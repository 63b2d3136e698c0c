"""Olmo-Hybrid family (gated delta-rule linear-attention layers, three to
every full-attention layer): what the program is given for a configuration
of this family, and what the algorithm needs of the chip.

Two halves, as ``llama_dense``.  ``model_config`` and ``make_params`` turn a
configuration file (the published ``config.json`` keys, and under
``assumed`` what that file lacks) into what the program takes.  Everything
above them is plain arithmetic on the published sizes, the benchmark's own
count of the operations and bytes a call requires; it imports nothing of the
program, so no change to the program moves it.

Names the metric readers use: the linear-attention layer's parts in the
device trace are ``lin_attn/proj``, ``lin_attn/conv``, ``lin_attn/gates``,
``lin_attn/state`` (a decode step's state update, the Pallas kernel
``gated_delta_update``; a prefill's chunked scan) and ``lin_attn/out``.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}
PARTS_PREFIX = "lin_attn/"
STATE_PART = "lin_attn/state"
STATE_KERNEL = "gated_delta_update"
# decode steps the engine may run past a sequence's last token before it
# lets the slot go (it chains up to 8 steps a fetch, ``engine._decode_all``):
# a released slot's rows have taken in that many tokens more, at most
DECODE_OVERSHOOT = 7


# --------------------------------------------------------------------------
# sizes (plain arithmetic; ``c`` is the configuration file as a dict)

def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def layer_types(c: dict) -> list:
    """The kinds of the layers that are run: the published list, as far as
    ``num_hidden_layers`` reaches."""
    return c["layer_types"][:c["num_hidden_layers"]]


def linear_layers(c: dict) -> int:
    return layer_types(c).count("linear_attention")


def full_layers(c: dict) -> int:
    return layer_types(c).count("full_attention")


def period(c: dict) -> int:
    """Layers a period: the linear layers before a full one, and it."""
    return layer_types(c).index("full_attention") + 1


def _linear_widths(c: dict):
    h = c["linear_num_value_heads"]
    return h, h * c["linear_key_head_dim"], h * c["linear_value_head_dim"]


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def linear_mixer_matmul_params(c: dict) -> int:
    """q, k; v, the output gate, the output projection; b, a."""
    d = c["hidden_size"]
    h, hk, hv = _linear_widths(c)
    return d * (2 * hk + 3 * hv + 2 * h)


def linear_layer_params(c: dict) -> int:
    d = c["hidden_size"]
    h, hk, hv = _linear_widths(c)
    small = (c["linear_conv_kernel_dim"] * (2 * hk + hv) + 2 * h
             + c["linear_value_head_dim"] + 2 * d)
    return linear_mixer_matmul_params(c) + mlp_params(c) + small


def full_mixer_matmul_params(c: dict) -> int:
    d = c["hidden_size"]
    hq = c["num_attention_heads"] * head_dim(c)
    hkv = c["num_key_value_heads"] * head_dim(c)
    return 2 * d * hq + 2 * d * hkv


def full_layer_params(c: dict) -> int:
    d = c["hidden_size"]
    hq = c["num_attention_heads"] * head_dim(c)
    hkv = c["num_key_value_heads"] * head_dim(c)
    return full_mixer_matmul_params(c) + mlp_params(c) + hq + hkv + 2 * d


def n_params(c: dict) -> int:
    d = c["hidden_size"]
    return (linear_layers(c) * linear_layer_params(c)
            + full_layers(c) * full_layer_params(c)
            + 2 * c["vocab_size"] * d + d)


def matmul_params(c: dict) -> int:
    """Parameters a token is multiplied with: everything but the embedding
    table (a lookup), the norms, the convolution's taps and the gates'
    scalars."""
    return (linear_layers(c) * (linear_mixer_matmul_params(c) + mlp_params(c))
            + full_layers(c) * (full_mixer_matmul_params(c) + mlp_params(c))
            + c["vocab_size"] * c["hidden_size"])


def weight_bytes(c: dict, dtype: str = "bfloat16") -> int:
    return n_params(c) * BYTES[dtype]


def kv_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    """K and V of one token over the FULL layers, at the model's own KV
    heads (the program's pages hold them padded to a multiple of 8: 32 for
    30, which is the program's cost and not required work)."""
    return (2 * full_layers(c) * c["num_key_value_heads"] * head_dim(c)
            * BYTES[dtype])


def state_bytes_per_layer(c: dict) -> int:
    """One slot's recurrent state of one linear layer: H x d_v x d_k,
    float32 (the configuration's ``assumed`` (c))."""
    return (c["linear_num_value_heads"] * c["linear_value_head_dim"]
            * c["linear_key_head_dim"] * BYTES["float32"])


def state_bytes_per_slot(c: dict, dtype: str = "bfloat16") -> int:
    """What one slot holds beside its pages: every linear layer's state and
    the convolution's last inputs (width - 1 rows of q~ k~ v~)."""
    h, hk, hv = _linear_widths(c)
    conv = (c["linear_conv_kernel_dim"] - 1) * (2 * hk + hv) * BYTES[dtype]
    return linear_layers(c) * (state_bytes_per_layer(c) + conv)


# --------------------------------------------------------------------------
# required operations and bytes

def state_update_bytes(c: dict, slots: float) -> float:
    """HBM bytes the state updates of ONE decode step have to move for
    ``slots`` live sequences: each one's state of every linear layer read
    once and written once."""
    return 2.0 * slots * linear_layers(c) * state_bytes_per_layer(c)


def delta_rule_flops_per_token(c: dict) -> float:
    """Step 5 for one token, all linear layers, as the recurrence states
    it: S k, the rank-one update with its decay, S q: 7 d_k d_v a head."""
    return (7.0 * c["linear_num_value_heads"] * c["linear_key_head_dim"]
            * c["linear_value_head_dim"] * linear_layers(c))


def attention_flops(c: dict, q_len: int, kv_len: int,
                    causal_within: bool) -> float:
    """QK^T and PV of the FULL layers (``llama_dense.attention_flops``)."""
    hq = c["num_attention_heads"] * head_dim(c)
    pairs = q_len * kv_len
    if causal_within:
        pairs -= q_len * (q_len - 1) / 2
    return 4.0 * pairs * hq * full_layers(c)


def prefill_flops(c: dict, new_tokens: int, cached_tokens: int = 0) -> float:
    """Forward pass over ``new_tokens`` prompt tokens (a prefill of this
    family never has cached predecessors: no prefix hit is taken).  The
    output head runs for the last position only."""
    d = c["hidden_size"]
    body = 2.0 * new_tokens * (matmul_params(c) - c["vocab_size"] * d)
    return (body + 2.0 * c["vocab_size"] * d
            + new_tokens * delta_rule_flops_per_token(c)
            + attention_flops(c, new_tokens, cached_tokens + new_tokens,
                              causal_within=True))


def decode_step_bytes(c: dict, context_tokens: float,
                      dtype: str = "bfloat16", slots: float = 0.0) -> float:
    """HBM bytes one decode step has to move: every weight a token is
    multiplied with, once, the K and V of the tokens present in the full
    layers, and the live slots' states, read and written."""
    return (matmul_params(c) * BYTES[dtype]
            + context_tokens * kv_bytes_per_token(c, dtype)
            + state_update_bytes(c, slots))


# --------------------------------------------------------------------------
# what the program is given (imports the program; runs in the chip's holder)

def model_module():
    from ray_tpu.models import olmo_hybrid

    return olmo_hybrid


def model_config(c: dict, **overrides):
    olmo_hybrid = model_module()  # a program without this family fails here
    kinds, p = layer_types(c), period(c)
    if kinds != (["linear_attention"] * (p - 1) + ["full_attention"]) \
            * (len(kinds) // p):
        raise ValueError(f"layer_types is not whole periods of {p}: {kinds}")
    return olmo_hybrid.OlmoHybridConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
        d_ff=c["intermediate_size"], lin_heads=c["linear_num_value_heads"],
        lin_key_dim=c["linear_key_head_dim"],
        lin_value_dim=c["linear_value_head_dim"],
        conv_width=c["linear_conv_kernel_dim"], period=p,
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype=c.get("dtype", "bfloat16"), **overrides)


def make_params(c: dict, seed: int, dtype: str):
    """Seeded weights in the type they are served in, made on the device in
    one jitted call (``rbg`` keys, as ``llama_dense.make_params``); the
    decays' ``A_log`` and ``dt_bias`` stay float32."""
    import jax
    import jax.numpy as jnp

    olmo_hybrid, cfg = model_module(), model_config(c)
    return jax.jit(lambda k: olmo_hybrid.init(cfg, k, jnp.dtype(dtype)))(
        jax.random.key(seed, impl="rbg"))


def recurrence_outputs(c: dict, q, k, v, g, beta, prefill_tokens: int):
    """The PROGRAM's two forms of step 5 on inputs HANDED IN (float32: q, k
    [T, H, d_k], v [T, H, d_v], g, beta [T, H]), as ``llm/model.py`` runs
    them: the prefill's chunked scan over the first ``prefill_tokens`` from
    a zero state, its final state packed into one slot's row of a state
    array of the dtype the model DECLARES (``cache_layout``), then the
    decode step's in-place kernel a token at a time, three idle slots
    beside the live one.  Returns o [T, H, d_v] float32.

    Why the inputs are pinned: over 16 layers in bf16 the served logits lie
    0.05-0.07 rms from the float32 reference's (PERF.md section 6, PR 38),
    which would bury a state kept in bf16 (0.002 of the state's own size);
    on the same float32 inputs the recurrence's two forms agree with the
    reference's token-by-token scan to 1e-5 or they are wrong."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import model as lm
    from ray_tpu.ops import gated_delta

    cfg = model_config(c)
    n, pack, slots, slot = prefill_tokens, cfg.state_pack, 4, 2
    _, row, dtype = lm.cache_layout(cfg)["state_rows"]["S"]
    o_prefill, S = gated_delta.chunked(q[:n], k[:n], v[:n], g[:n], beta[:n],
                                       jnp.zeros((q.shape[1], v.shape[2],
                                                  q.shape[2]), jnp.float32))
    state = jnp.zeros((1, slots, *row), dtype).at[0, slot].set(
        gated_delta.pack_state(S, pack).astype(dtype))
    active = jnp.arange(slots) == slot

    def step(state, x):
        o, state = gated_delta.decode_update(
            state, 0, *(jnp.broadcast_to(a, (slots, *a.shape)) for a in x),
            active, pack=pack)
        return state, o[slot]

    _, o_decode = jax.lax.scan(step, state, (q[n:], k[n:], v[n:], g[n:],
                                             beta[n:]))
    return jnp.concatenate([o_prefill, o_decode], axis=0)


def serving_params(params):
    """The tree in the layout the engine holds (it takes either): laid out
    by the LOADER, once the reference has read the unstacked weights, so
    that they are gone before the engine makes its pools (1.95 GB at the
    published widths, which would else stand beside their stacked copy
    under the pools until the server's constructor returns)."""
    from ray_tpu.llm import model as lm

    return lm.serving_layout(params)


def engine_states(engine, layer: int = 0):
    """The ENGINE's rows of one linear layer as they lie after whatever it
    last ran, every slot: [slots, H, d_v, d_k] float32, as step 5 writes
    S."""
    import jax.numpy as jnp

    from ray_tpu.ops import gated_delta

    return gated_delta.unpack_state(
        engine.state["S"][layer], engine.model_cfg.state_pack
    ).astype(jnp.float32)
