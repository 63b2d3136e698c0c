"""Llama-3-8B 1-chip-scaled measurement (BASELINE.json north star).

8B does not fit one v5e chip (weights+adam ~= 80GB vs 16GB HBM), so the
full-model step time is DERIVED from on-chip measurements at the real 8B
layer geometry (d_model 4096, d_ff 14336, 32q/8kv heads, seq 4096,
remat, flash attention, bf16 + fp32 adam):

  t_layer  — marginal cost of one decoder layer: (t(3L) - t(1L)) / 2.
             Layer FLOPs are vocab-independent, so this is exact.
  t_vocab  — marginal cost of 32k vocab rows in embed + chunked-loss
             head: t(1L, 64k) - t(1L, 32k).
  t_full   = t(1L, 32k) + 31 * t_layer + 3 * t_vocab   (128k vocab)

tokens/sec/chip = batch * seq / t_full.  Recorded in BASELINE.json as a
1-chip-scaled DERIVED number, labeled as such — it assumes linear layer
scaling (true under remat: layers are sequential and identical) and ICI
overheads of the real 16-chip run are NOT included.

Run: python scripts/bench_llama8b.py  (real chip; ~4 compiles)
"""

import json
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp


def step_time(n_layers: int, vocab: int, seq: int = 4096,
              reps: int = 3) -> float:
    from dataclasses import replace

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import single_device_mesh
    from ray_tpu.train.step import (
        create_train_state,
        default_optimizer,
        make_train_step,
    )

    cfg = replace(llama.LlamaConfig.llama3_8b(), n_layers=n_layers,
                  vocab_size=vocab, max_seq_len=seq)
    mesh = single_device_mesh()
    opt = default_optimizer()
    with mesh:
        state = create_train_state(llama, cfg, mesh, opt,
                                   jax.random.PRNGKey(0))
        step = make_train_step(llama, cfg, mesh, opt, attn_impl="flash")
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, seq + 1),
                                    0, vocab, dtype=jnp.int32)
        state, m = step(state, tokens)  # compile
        jax.block_until_ready(m["loss"])
        # one discarded rep: the first step after the compile is not yet
        # steady state
        state, m = step(state, tokens)
        jax.block_until_ready(m["loss"])
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            state, m = step(state, tokens)
            jax.block_until_ready(m["loss"])
            best = min(best, time.perf_counter() - t0)
    del state
    return best


def main():
    seq = 4096
    t1_32k = step_time(1, 32768, seq)
    print(f"t(1L, 32k) = {t1_32k * 1e3:.1f} ms", flush=True)
    t3_32k = step_time(3, 32768, seq)
    print(f"t(3L, 32k) = {t3_32k * 1e3:.1f} ms", flush=True)
    t1_64k = step_time(1, 65536, seq)
    print(f"t(1L, 64k) = {t1_64k * 1e3:.1f} ms", flush=True)

    t_layer = (t3_32k - t1_32k) / 2
    t_vocab32k = max(0.0, t1_64k - t1_32k)
    t_full = t1_32k + 31 * t_layer + 3 * t_vocab32k
    tok_s = seq / t_full
    # model FLOPs: ~6 * n_params * tokens (fwd+bwd), 8.03B params
    mfu_tflops = 6 * 8.03e9 * tok_s / 1e12
    out = {
        "llama3_8b_tokens_per_sec_chip_derived": round(tok_s, 1),
        "derivation": {
            "seq": seq, "t_1layer_32k_ms": round(t1_32k * 1e3, 1),
            "t_3layer_32k_ms": round(t3_32k * 1e3, 1),
            "t_1layer_64k_ms": round(t1_64k * 1e3, 1),
            "t_marginal_layer_ms": round(t_layer * 1e3, 2),
            "t_marginal_32kvocab_ms": round(t_vocab32k * 1e3, 2),
            "t_full_step_est_ms": round(t_full * 1e3, 1),
            "model_tflops_per_s": round(mfu_tflops, 1),
        },
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
