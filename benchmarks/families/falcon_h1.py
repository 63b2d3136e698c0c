"""Falcon-H1 family (a Mamba-2 mixer beside grouped-query attention in every
block): what the program is given for a configuration of this family, what
the algorithm needs of the chip, and the faults a control plants.

Two halves, as ``olmo_hybrid``.  ``model_config`` and ``make_params`` turn a
configuration file (the published ``config.json`` keys, and under
``assumed`` what that file lacks) into what the program takes.  Everything
above them is plain arithmetic on the published sizes, the benchmark's own
count of the operations and bytes a call requires; it imports nothing of the
program, so no change to the program moves it.

Names the metric readers use: the mixer's parts in the device trace are
``ssm/proj`` (W_in and the muP vector), ``ssm/conv``, ``ssm/gates``
(softplus, decay, dt x), ``ssm/state`` (a decode step's update, the Pallas
kernel ``lightning_update``; a prefill's chunked scan; D x) and ``ssm/out``
(gated norm and W_out); attention's and the MLP's are ``attn/*`` and
``mlp/*`` as every family's.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}
PARTS_PREFIX = "ssm/"
STATE_PART = "ssm/state"

# The CONTROLS: faults planted in what ``correct`` compares
# (``runners/serve_parallel_ssm.py`` says which comparison catches which).
FAULTS = ("state_in_bf16", "key_multiplier_left_out", "mup_vector_left_out",
          "dt_bias_left_out", "gate_after_norm", "tail_one_late")


# --------------------------------------------------------------------------
# sizes (plain arithmetic; ``c`` is the configuration file as a dict)

def mixer_sizes(c: dict) -> tuple:
    """(heads, a head's width, the state's size, groups, x + B + C)."""
    h, p, n, g = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                  c["mamba_n_groups"])
    return h, p, n, g, h * p + 2 * g * n


def attention_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return d * hq + 2 * d * hkv + hq * d


def mixer_matmul_params(c: dict) -> int:
    """W_in (d -> z | x | B | C | dt) and W_out."""
    h, p, _, _, xbc = mixer_sizes(c)
    return c["hidden_size"] * (h * p + xbc + h) + h * p * c["hidden_size"]


def mixer_params(c: dict) -> int:
    """The two products, the taps and the convolution's bias, dt_bias,
    A_log and D a head, the gated norm's weight."""
    h, p, _, _, xbc = mixer_sizes(c)
    return (mixer_matmul_params(c) + (c["mamba_d_conv"] + 1) * xbc + 3 * h
            + h * p)


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_params(c: dict) -> int:
    """A block: attention, mixer, MLP and the two norms."""
    return (attention_params(c) + mixer_params(c) + mlp_params(c)
            + 2 * c["hidden_size"])


def n_params(c: dict) -> int:
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def weight_bytes(c: dict, dtype: str = "bfloat16") -> int:
    return n_params(c) * BYTES[dtype]


def kv_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    """K and V of every layer for one cached token."""
    return (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * BYTES[dtype])


def state_bytes_per_layer(c: dict) -> int:
    """One slot's recurrent state of one layer: H x N x P, float32 (the
    configuration's ``assumed``)."""
    h, p, n, _, _ = mixer_sizes(c)
    return h * n * p * BYTES["float32"]


def tail_bytes_per_layer(c: dict, dtype: str = "bfloat16") -> int:
    """The convolution's last ``mamba_d_conv - 1`` inputs of one layer."""
    return (c["mamba_d_conv"] - 1) * mixer_sizes(c)[4] * BYTES[dtype]


def state_bytes_per_slot(c: dict, dtype: str = "bfloat16") -> int:
    """What one slot holds beside its pages."""
    return c["num_hidden_layers"] * (
        state_bytes_per_layer(c) + tail_bytes_per_layer(c, dtype))


# --------------------------------------------------------------------------
# required operations and bytes

def state_update_bytes(c: dict, slots: float) -> float:
    """HBM bytes the state updates of ONE decode step have to move for
    ``slots`` live sequences: each one's float32 state of every layer read
    once and written once (the work required, whatever implements it)."""
    return 2.0 * slots * c["num_hidden_layers"] * state_bytes_per_layer(c)


def matmul_params(c: dict) -> int:
    """Parameters a token's products read (the head's, not the
    embedding's)."""
    return (c["num_hidden_layers"] * (
        attention_params(c) + mixer_matmul_params(c) + mlp_params(c))
        + c["vocab_size"] * c["hidden_size"])


def scan_flops_per_token(c: dict) -> float:
    """The recurrence itself for one token, every layer: the write
    ``B (dt x)^T`` and the read ``C S``, a multiply-add a state element
    each."""
    h, p, n, _, _ = mixer_sizes(c)
    return 4.0 * c["num_hidden_layers"] * h * n * p


def attention_flops(c: dict, q_len: int, kv_len: int) -> float:
    """QK^T and PV of ``q_len`` new tokens against ``kv_len`` cached ones
    and causally against each other, every layer."""
    pairs = q_len * kv_len + q_len * (q_len + 1) / 2.0
    return (4.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * pairs)


def prefill_flops(c: dict, new_tokens: int, cached_tokens: int = 0) -> float:
    """FLOPs a prefill of ``new_tokens`` requires: every layer's products
    for each token, the head for the last, the recurrence, attention."""
    layers = matmul_params(c) - c["vocab_size"] * c["hidden_size"]
    return (2.0 * layers * new_tokens
            + 2.0 * c["vocab_size"] * c["hidden_size"]
            + scan_flops_per_token(c) * new_tokens
            + attention_flops(c, new_tokens, cached_tokens))


# --------------------------------------------------------------------------
# what the program is given (imports the program; runs in the chip's holder)

def model_module():
    from ray_tpu.models import falcon_h1

    return falcon_h1


def model_config(c: dict, **overrides):
    falcon_h1 = model_module()  # a program without this family fails here
    h, p, n, g, _ = mixer_sizes(c)
    if c["mamba_d_ssm"] != h * p:
        raise ValueError(f"mamba_d_ssm {c['mamba_d_ssm']} is not "
                         f"{h} heads of {p}")
    return falcon_h1.FalconH1Config(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], ssm_heads=h, ssm_head_dim=p,
        ssm_state=n, ssm_groups=g, conv_width=c["mamba_d_conv"],
        embedding_multiplier=c["embedding_multiplier"],
        lm_head_multiplier=c["lm_head_multiplier"],
        key_multiplier=c["key_multiplier"],
        attention_in_multiplier=c["attention_in_multiplier"],
        attention_out_multiplier=c["attention_out_multiplier"],
        ssm_in_multiplier=c["ssm_in_multiplier"],
        ssm_out_multiplier=c["ssm_out_multiplier"],
        ssm_multipliers=tuple(c["ssm_multipliers"]),
        mlp_multipliers=tuple(c["mlp_multipliers"]),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype=c.get("dtype", "bfloat16")), **overrides})


def make_params(c: dict, seed: int, dtype: str):
    """Seeded weights in the type they are served in, made on the device in
    one jitted call (``rbg`` keys, as ``llama_dense.make_params``) and laid
    out as they are served (one ``wqkv``); ``A_log``, ``dt_bias`` and ``D``
    stay float32.  The WEIGHTS are the published configuration's whatever a
    control overrides: a fault is in the program, not in the weights."""
    import jax
    import jax.numpy as jnp

    falcon_h1, cfg = model_module(), model_config(c)
    return jax.jit(lambda k: cfg.serving_layout(
        falcon_h1.init(cfg, k, jnp.dtype(dtype))))(
            jax.random.key(seed, impl="rbg"))


def plant(fault: str, piece: int = 256):
    """``fault`` into the program, in THIS process, before it compiles:
    (what ``model_config`` is to be overridden with, the function that
    takes the fault out again).  A control's, never a run's.

    ``state_in_bf16``: the recurrence, both forms, keeps its state rounded
    to bf16 (every step of the decode form, every ``piece`` tokens of the
    chunked one: a state stored in bf16 is rounded wherever it is stored),
    the nearest precision below the configuration's float32.
    ``key_multiplier_left_out``: the keys as the product leaves them.
    ``mup_vector_left_out``: the five segments of the mixer's input
    unscaled.  ``dt_bias_left_out``: ``dt = softplus(dt)``.
    ``gate_after_norm``: the group norm before the gate.
    ``tail_one_late``: the convolution's rows kept a token late (a decode
    step then convolves over inputs that are one position old)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import falcon_h1 as fh
    from ray_tpu.models import olmo_hybrid
    from ray_tpu.models.llama import rms_norm
    from ray_tpu.ops import lightning

    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")
    overrides, swaps = {}, []
    if fault == "key_multiplier_left_out":
        overrides = {"key_multiplier": 1.0}
    elif fault == "mup_vector_left_out":
        overrides = {"ssm_multipliers": (1.0,) * 5}
    elif fault == "state_in_bf16":  # the same recurrence file, the same
        # fault as ``families/minicpm_sala.py`` plants (below: it swaps)
        swaps = [(lightning, "chunked", None),
                 (lightning, "decode_update", None)]
    elif fault == "dt_bias_left_out":
        mixer = fh.mixer
        swaps = [(fh, "mixer", lambda cfg, p, *a: mixer(
            cfg, {**p, "dt_bias": jnp.zeros_like(p["dt_bias"])}, *a))]
    elif fault == "gate_after_norm":

        def norm_first(cfg, p, y, z):
            f32, G = jnp.float32, cfg.ssm_groups
            y = rms_norm(y.reshape(*y.shape[:-1], G, cfg.d_ssm // G),
                         jnp.ones((), f32), cfg.norm_eps).reshape(y.shape)
            return y * p["norm"].astype(f32) * jax.nn.silu(z.astype(f32))

        swaps = [(fh, "gated_norm", norm_first)]
    elif fault == "tail_one_late":
        conv = olmo_hybrid.short_conv

        def late(*a, **kw):
            y, rows = conv(*a, **kw)
            return y, jnp.roll(rows, 1, axis=0)

        swaps = [(olmo_hybrid, "short_conv", late)]
    old = [(holder, name, getattr(holder, name)) for holder, name, _ in swaps]
    if fault == "state_in_bf16":
        from benchmarks.families import minicpm_sala

        minicpm_sala.plant_state_bf16(piece)
    else:
        for holder, name, new in swaps:
            setattr(holder, name, new)

    def undo():
        for holder, name, was in old:
            setattr(holder, name, was)

    return overrides, undo


def engine_rows(engine, pages: list):
    """What the engine's pools hold for one sequence through ``pages``:
    ``k``, ``v`` [layers, len(pages) x page_size, KV heads, d]."""
    import jax.numpy as jnp

    idx = jnp.asarray(pages, jnp.int32)
    k, v = (pool[:, idx].reshape(pool.shape[0], -1, *pool.shape[3:])
            for pool in (engine.cache_k, engine.cache_v))
    return {"k": k, "v": v}


def engine_state(engine, slot: int):
    """``slot``'s rows: ``S`` [layers, H, N, P] float32 and ``conv``
    [layers, taps, x + B + C], the convolution's last inputs."""
    S, conv = engine.state["S"], engine.state["conv"]
    return {"S": S[:, slot],
            "conv": conv[:, slot].reshape(S.shape[0], -1, conv.shape[-1])}
