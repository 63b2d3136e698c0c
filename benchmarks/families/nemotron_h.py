"""Nemotron-H family (``nemotron_h``: Nemotron-3-Super; layers that are ONE
thing each, a Mamba-2 mixer OR grouped-query attention without positions OR
a LatentMoE feed-forward whose experts a chip HOLDS A SHARE of): what the
program is given for a configuration of this family, what the algorithm
needs of the chip, and the faults a control plants.

Two halves, as ``falcon_h1`` and ``longcat_flash``.  ``model_config``,
``make_params``, ``routed_part``, ``engine_rows`` and ``engine_state`` turn a
configuration file (the published ``config.json`` keys, and under
``assumed`` what that file lacks) into what the program takes.  Everything
above them is plain arithmetic on the published sizes and the share, the
benchmark's own count of the operations and bytes a call requires; it
imports nothing of the program, so no change to the program moves it.

The file's ``n_routed_experts`` is what THIS CHIP HOLDS (128); the router's
columns are ``published.n_routed_experts`` (512); ``vocab_size`` is the
share's slice of the vocabulary; ``hybrid_override_pattern`` is the period
that is run and ``num_hidden_layers`` its length.

Names the metric readers use: a decode step is a program
``jit_decode_step*`` in the device trace; the mixer's parts are ``ssm/proj``,
``ssm/conv``, ``ssm/gates``, ``ssm/state`` (a decode step's update, the
Pallas kernel ``lightning_update``; a prefill's chunked scan; D x) and
``ssm/out``; the routed layer's ``moe/route``, ``moe/latent_in``,
``moe/dispatch``, ``moe/experts`` (the grouped kernel over the held
experts), ``moe/combine``, ``moe/latent_out`` and ``moe/shared``;
attention's ``attn/*``.  ``LATENT_PARTS`` is what tells this family's decode
programs to ``layer_metrics/_latent.py`` (there a latent ATTENTION's parts,
here the latent the EXPERTS live in): ``moe_decode_share`` reads through it.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}
DECODE_MODULE = "jit_decode_step"
PARTS_PREFIX = "ssm/"
STATE_PART = "ssm/state"
MOE_PARTS_PREFIX = "moe/"
EXPERT_KERNEL_PART = "moe/experts"
LATENT_PROJ_PARTS = ("moe/latent_in", "moe/latent_out")
LATENT_PARTS = LATENT_PROJ_PARTS
LATENT_KERNEL_PART = EXPERT_KERNEL_PART
KINDS = ("M", "*", "E")

# The CONTROLS: faults planted in what ``correct`` compares
# (``runners/serve_latent_moe_ssm.py`` says which comparison catches which).
FAULTS = ("state_in_bf16", "relu_not_squared", "experts_gated_silu",
          "scale_left_out", "bias_left_out", "shared_on_latent",
          "rope_applied", "tail_one_late")


# --------------------------------------------------------------------------
# sizes (plain arithmetic; ``c`` is the configuration file as a dict)

def pattern(c: dict) -> str:
    return c["hybrid_override_pattern"]


def count(c: dict, kind: str) -> int:
    return pattern(c).count(kind)


def n_layers(c: dict) -> tuple:
    """(layers that route nothing, routed layers)."""
    return len(pattern(c)) - count(c, "E"), count(c, "E")


def router_columns(c: dict) -> int:
    """The router's columns: the WHOLE model's experts."""
    return c.get("published", {}).get("n_routed_experts",
                                      c["n_routed_experts"])


def mixer_sizes(c: dict) -> tuple:
    """(heads, a head's width, the state's size, groups, x + B + C)."""
    h, p, n, g = (c["mamba_num_heads"], c["mamba_head_dim"],
                  c["ssm_state_size"], c["n_groups"])
    return h, p, n, g, h * p + 2 * g * n


def mixer_matmul_params(c: dict) -> int:
    """W_in (d -> z | x | B | C | dt) and W_out."""
    h, p, _, _, xbc = mixer_sizes(c)
    return c["hidden_size"] * (h * p + xbc + h) + h * p * c["hidden_size"]


def mixer_params(c: dict) -> int:
    """A ``M`` layer: the two products, the taps and the convolution's
    bias, dt_bias, A_log and D a head, the gated norm's weight, the
    layer's norm."""
    h, p, _, _, xbc = mixer_sizes(c)
    return (mixer_matmul_params(c) + (c["conv_kernel"] + 1) * xbc + 3 * h
            + h * p + c["hidden_size"])


def attention_params(c: dict) -> int:
    """A ``*`` layer: W_q, W_k, W_v, W_o and the layer's norm."""
    d, hd = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return d * hq + 2 * d * hkv + hq * d + d


def expert_params(c: dict) -> int:
    """One routed expert: up and down, in the latent."""
    return 2 * c["moe_latent_size"] * c["moe_intermediate_size"]


def routed_shared_params(c: dict) -> int:
    """An ``E`` layer OUTSIDE its experts, whole on every chip: the router
    over all its columns and its bias, both latent projections, the shared
    expert, the layer's norm."""
    d, r = c["hidden_size"], c["moe_latent_size"]
    cols = router_columns(c)
    return (d * cols + cols + 2 * d * r
            + 2 * d * c["moe_shared_expert_intermediate_size"] + d)


def routed_params(c: dict, experts: float = None) -> float:
    """An ``E`` layer as this chip holds it (``experts``: counting only so
    many of the held ones)."""
    n = c["n_routed_experts"] if experts is None else experts
    return routed_shared_params(c) + n * expert_params(c)


def n_params(c: dict) -> int:
    d = c["hidden_size"]
    return int(count(c, "M") * mixer_params(c)
               + count(c, "*") * attention_params(c)
               + count(c, "E") * routed_params(c)
               + 2 * c["vocab_size"] * d + d)


def weight_bytes(c: dict, dtype: str = "bfloat16") -> int:
    return n_params(c) * BYTES[dtype]


def kv_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    """K and V of every ``*`` layer for one cached token."""
    return (count(c, "*") * 2 * c["num_key_value_heads"] * c["head_dim"]
            * BYTES[dtype])


def state_bytes_per_layer(c: dict) -> int:
    """One slot's recurrent state of one ``M`` layer: H x N x P, float32
    (the configuration's ``assumed``), whatever layout holds it."""
    h, p, n, _, _ = mixer_sizes(c)
    return h * n * p * BYTES["float32"]


def tail_bytes_per_layer(c: dict, dtype: str = "bfloat16") -> int:
    """The convolution's last ``conv_kernel - 1`` inputs of one layer."""
    return (c["conv_kernel"] - 1) * mixer_sizes(c)[4] * BYTES[dtype]


def state_bytes_per_slot(c: dict, dtype: str = "bfloat16") -> int:
    """What one slot holds beside its pages."""
    return count(c, "M") * (state_bytes_per_layer(c)
                            + tail_bytes_per_layer(c, dtype))


# --------------------------------------------------------------------------
# required operations and bytes

def state_update_bytes(c: dict, slots: float) -> float:
    """HBM bytes the state updates of ONE decode step have to move for
    ``slots`` live sequences: each one's float32 state of every ``M`` layer
    read once and written once (the work required, whatever layout the
    program keeps: a padded layout moves more and reads under this)."""
    return 2.0 * slots * count(c, "M") * state_bytes_per_layer(c)


def expected_experts_hit(c: dict, rows: float) -> float:
    """HELD experts of one layer that at least one of ``rows`` tokens
    reaches, the router taken as uniform over its columns."""
    held, cols = c["n_routed_experts"], router_columns(c)
    return held * (1.0 - (1.0 - c["num_experts_per_tok"] / cols) ** rows)


def expected_local_rows(c: dict, rows: float) -> float:
    """(token, expert) rows the held experts of one layer compute."""
    return (rows * c["num_experts_per_tok"] * c["n_routed_experts"]
            / router_columns(c))


def expert_bytes_per_call(c: dict, local_rows: float, experts_hit: float,
                          dtype: str = "bfloat16") -> float:
    """HBM bytes ONE call of the grouped product (one layer) has to move:
    the two matrices of the ``experts_hit`` held experts some token
    reaches, once, plus each of the ``local_rows`` latent rows in and
    out."""
    rows_io = 2 * local_rows * c["moe_latent_size"]
    return (experts_hit * expert_params(c) + rows_io) * BYTES[dtype]


def matmul_params(c: dict, experts_hit: float = 0.0) -> float:
    """Parameters a step's products read (the head's, not the embedding's),
    ``experts_hit`` held experts a routed layer."""
    return (count(c, "M") * mixer_matmul_params(c)
            + count(c, "*") * attention_params(c)
            + count(c, "E") * routed_params(c, experts_hit)
            + c["vocab_size"] * c["hidden_size"])


def decode_step_bytes(c: dict, slots: float, context_tokens: float,
                      experts_hit: float = None,
                      dtype: str = "bfloat16") -> float:
    """HBM bytes one decode step has to move: every layer's weights with
    the held experts ``slots`` tokens reach (``experts_hit`` a layer, else
    what uniform routing gives), the head's slice, the live slots' state
    read and written, and K and V of the tokens present."""
    if experts_hit is None:
        experts_hit = expected_experts_hit(c, slots)
    return (matmul_params(c, experts_hit) * BYTES[dtype]
            + state_update_bytes(c, slots)
            + context_tokens * kv_bytes_per_token(c, dtype))


def scan_flops_per_token(c: dict) -> float:
    """The recurrence itself for one token, every ``M`` layer."""
    h, p, n, _, _ = mixer_sizes(c)
    return 4.0 * count(c, "M") * h * n * p


def attention_flops(c: dict, q_len: int, kv_len: int) -> float:
    """QK^T and PV of ``q_len`` new tokens against ``kv_len`` cached ones
    and causally against each other, every ``*`` layer."""
    pairs = q_len * kv_len + q_len * (q_len + 1) / 2.0
    return (4.0 * count(c, "*") * c["num_attention_heads"] * c["head_dim"]
            * pairs)


def prefill_flops(c: dict, new_tokens: int, cached_tokens: int = 0) -> float:
    """FLOPs a prefill of ``new_tokens`` requires ON THIS CHIP: every
    layer's products for each token (of a token's picks the share that
    lands on held experts with a uniform router), the head for the last,
    the recurrence, attention."""
    per_token = (matmul_params(c) - c["vocab_size"] * c["hidden_size"]
                 + count(c, "E") * expected_local_rows(c, 1.0)
                 * expert_params(c))
    return (2.0 * per_token * new_tokens
            + 2.0 * c["vocab_size"] * c["hidden_size"]
            + scan_flops_per_token(c) * new_tokens
            + attention_flops(c, new_tokens, cached_tokens))


# --------------------------------------------------------------------------
# what the program is given (imports the program; runs in the chip's holder)

def model_module():
    from ray_tpu.models import nemotron_h

    return nemotron_h


def model_config(c: dict, **overrides):
    nh = model_module()  # a program without this family fails here
    if len(pattern(c)) != c["num_hidden_layers"]:
        raise ValueError(f"hybrid_override_pattern {pattern(c)!r} is not "
                         f"{c['num_hidden_layers']} layers")
    if c["mlp_hidden_act"] != "relu2" or c["n_shared_experts"] != 1 \
            or c["n_group"] != 1 or c["topk_group"] != 1 \
            or not c["norm_topk_prob"]:
        raise ValueError("written for relu2 experts beside ONE shared "
                         "expert, a router of one group that renormalises")
    h, p, n, g, _ = mixer_sizes(c)
    return nh.NemotronHConfig(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        pattern=pattern(c), n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        ssm_heads=h, ssm_head_dim=p, ssm_state=n, ssm_groups=g,
        conv_width=c["conv_kernel"], n_experts=router_columns(c),
        experts_per_token=c["num_experts_per_tok"],
        d_latent=c["moe_latent_size"], d_expert=c["moe_intermediate_size"],
        d_shared=c["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        n_experts_held=c["n_routed_experts"],
        first_expert_held=c.get("first_expert_held", 0),
        max_seq_len=c["max_position_embeddings"],
        norm_eps=float(c["layer_norm_epsilon"]),
        dtype=c.get("dtype", "bfloat16")),
        **({"state_lanes": c["state_lanes"]} if "state_lanes" in c else {}),
        **overrides})


def make_params(c: dict, seed: int, dtype: str):
    """Seeded weights in the type they are served in, made on the device in
    one jitted call (``rbg`` keys, as ``llama_dense.make_params``) and laid
    out as they are served; the model's own ``init`` draws the router at
    ``router_logit_sd`` and the non-zero ``router_bias`` at
    ``router_bias_sd`` (both under ``assumed`` in the file).  The WEIGHTS
    are the configuration's whatever a control overrides: a fault is in the
    program, not in the weights."""
    import jax
    import jax.numpy as jnp

    nh, cfg = model_module(), model_config(c)
    return jax.jit(lambda k: cfg.serving_layout(nh.init(
        cfg, k, jnp.dtype(dtype), float(c["router_bias_sd"]),
        float(c["router_logit_sd"]))))(jax.random.key(seed, impl="rbg"))


def routed_part(c: dict, params, u, pinned=None):
    """The PROGRAM's held experts' part ``r W_lout`` of every ``E`` layer
    (its router unless ``pinned``, its latent projections, its sort,
    grouped kernel and combine) on rows HANDED IN: u [E layers, n, d]
    float32 (the reference's normed rows, cast to the served type);
    ``pinned`` = (weights, chosen) each [E layers, n, k].  Returns (the
    parts [E layers, n, d] float32, the routing the program used: weights
    and chosen [E layers, n, k])."""
    import jax.numpy as jnp

    nh, cfg = model_module(), model_config(c)
    layers, out, ws, es = params["layers"], [], [], []
    for j in range(count(c, "E")):
        p, uf = layers["E"][j], u[j].astype(jnp.dtype(c["dtype"]))
        routing = (pinned[0][j], pinned[1][j]) if pinned \
            else nh.route(cfg, p, uf)
        part, _ = nh.routed_part(cfg, p, layers["experts"], j, uf, routing)
        out.append(part.astype(jnp.float32))
        ws.append(routing[0])
        es.append(routing[1])
    return jnp.stack(out), jnp.stack(ws), jnp.stack(es)


def plant(fault: str, piece: int = 256):
    """``fault`` into the program, in THIS process, before it compiles:
    (what ``model_config`` is to be overridden with, the function that
    takes the fault out again).  A control's, never a run's.

    ``state_in_bf16``: the recurrence, both forms, keeps its state rounded
    to bf16 (``families/minicpm_sala.plant_state_bf16``: every step of the
    decode form, every ``piece`` tokens of the chunked one), the nearest
    precision below the configuration's float32.  ``relu_not_squared``: an
    expert's and the shared expert's ``relu(x W_up) W_down``.
    ``experts_gated_silu``: the routed experts in the OLD form, ``silu(x
    W_up) * (x W_up) W_down`` (a third matrix's worth of the gated kernel,
    the gate's weights the up-projection's).  ``scale_left_out``: the picks'
    weights sum to 1, not to ``routed_scaling_factor``.
    ``bias_left_out``: the choice by the scores alone.
    ``shared_on_latent``: the shared expert fed the latent's round trip
    ``(u W_lin) W_lout`` in the place of u.  ``rope_applied``: q and k
    rotated (rotate-half at ``rope_theta``).  ``tail_one_late``: the
    convolution's rows kept a token late."""
    import jax.numpy as jnp

    from ray_tpu.models import llama, moe, olmo_hybrid
    from ray_tpu.models import nemotron_h as nh
    from ray_tpu.ops import grouped_matmul, lightning

    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")
    overrides, swaps = {}, []
    if fault == "scale_left_out":
        route = moe.route
        swaps = [(moe, "route", lambda *a, scale=None, **kw: route(
            *a, scale=None, **kw))]
    elif fault == "state_in_bf16":
        swaps = [(lightning, "chunked", None),
                 (lightning, "decode_update", None)]
    elif fault == "relu_not_squared":
        hidden = grouped_matmul._hidden

        def plain(x, w_in):
            if len(w_in) != 1:
                return hidden(x, w_in)
            up = jnp.dot(x, w_in[0][...], preferred_element_type=jnp.float32)
            return jnp.maximum(up, 0.0).astype(x.dtype)

        def shared(p, hf):
            up = jnp.maximum(hf @ p["w_up"].astype(hf.dtype), 0)
            return up @ p["w_down"].astype(hf.dtype)

        swaps = [(grouped_matmul, "_hidden", plain),
                 (moe, "shared_mlp", shared)]
    elif fault == "experts_gated_silu":
        grouped = grouped_matmul.grouped_mlp
        swaps = [(grouped_matmul, "grouped_mlp",
                  lambda x, w_gate, w_up, *a, **kw: grouped(
                      x, w_up if w_gate is None else w_gate, w_up, *a,
                      **kw))]
    elif fault == "bias_left_out":
        route = moe.route
        swaps = [(moe, "route", lambda *a, bias=None, **kw: route(
            *a, bias=None if bias is None else jnp.zeros_like(bias), **kw))]
    elif fault == "shared_on_latent":
        part = nh.routed_part

        def layer(cfg, p, experts, i, x, pinned=None):
            u = llama.rms_norm(x, p["norm"], cfg.norm_eps)
            uf = u.reshape(-1, u.shape[-1])
            r, counted = part(cfg, p, experts, i, uf, pinned)
            trip = (uf @ p["w_lin"].astype(uf.dtype)) @ p["w_lout"].astype(
                uf.dtype)
            return x + (r + moe.shared_mlp(p["shared"], trip)).reshape(
                x.shape), counted

        swaps = [(nh, "latent_moe", layer)]
    elif fault == "rope_applied":
        qkv = llama.qkv

        def rotated(cfg, p, h, ring=None):
            q, k, v = qkv(cfg, p, h, ring)
            at = jnp.arange(q.shape[-3])  # (a decode step's: the slot's
            # index, which is position enough for a fault)
            return (llama.rope(q, at, 10000.0), llama.rope(k, at, 10000.0),
                    v)

        swaps = [(nh.llama, "qkv", rotated)]
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
    ``k``, ``v`` [* layers, len(pages) x page_size, KV heads, d]."""
    import jax.numpy as jnp

    idx = jnp.asarray(pages, jnp.int32)
    k, v = (pool[:, idx].reshape(pool.shape[0], -1, *pool.shape[3:])
            for pool in (engine.cache_k, engine.cache_v))
    return {"k": k, "v": v}


def engine_state(engine, slot: int):
    """``slot``'s rows: ``S`` [M layers, H, N, P] float32, a head at a time
    (out of the packed rows), and ``conv`` [M layers, taps, x + B + C], the
    convolution's last inputs."""
    from ray_tpu.ops import lightning

    S, conv = engine.state["S"], engine.state["conv"]
    return {"S": lightning.unpack_state(S[:, slot],
                                        engine.model_cfg.state_pack),
            "conv": conv[:, slot].reshape(S.shape[0], -1, conv.shape[-1])}
