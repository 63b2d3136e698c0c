"""Trinity-Mini's family (``afmoe``: window and full attention layers in one
stack, gated attention, leading dense layers, then sigmoid-scored routed
experts beside a shared one): what the program is given for a configuration
of this family, and what the algorithm needs of the chip.

Two halves, as ``glm4_moe_lite``.  ``model_config``, ``make_params``,
``pinned_logits`` and ``engine_rows`` turn a configuration file (the
published ``config.json`` keys) into what the program takes.  Everything
above them is plain arithmetic on the published sizes, the benchmark's own
count of the operations and bytes a call requires; it imports nothing of the
program, so no change to the program moves it.

Names the metric readers use: a decode step is a program ``jit_decode_step*``
in the device trace; the paged decode kernel's time (with its window bound
or without) lies under the part ``attn/attend`` and the routed experts'
grouped product under ``moe/experts`` of those programs.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}
DECODE_MODULE = "jit_decode_step"
ATTEND_PART = "attn/attend"
EXPERT_KERNEL_PART = "moe/experts"
MOE_PARTS_PREFIX = "moe/"
SLIDING = "sliding_attention"


# --------------------------------------------------------------------------
# sizes (plain arithmetic; ``c`` is the configuration file as a dict)

def attention_params_per_layer(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return 3 * d * hq + 2 * d * hkv + 2 * hd  # W_q, W_g, W_o; W_k, W_v; norms


def expert_params(c: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    return c["num_shared_experts"] * expert_params(c)


def dense_layer_params(c: dict) -> int:
    d = c["hidden_size"]
    return (attention_params_per_layer(c) + 3 * d * c["intermediate_size"]
            + 4 * d)


def sparse_layer_params(c: dict, experts: float = None) -> float:
    """A sparse layer; ``experts``: counting only so many of the routed."""
    d, e = c["hidden_size"], c["num_experts"]
    n = e if experts is None else experts
    return (attention_params_per_layer(c) + d * e + e  # router and its bias
            + shared_params(c) + n * expert_params(c) + 4 * d)


def n_layers(c: dict) -> tuple:
    """(dense, sparse)."""
    k = c["num_dense_layers"]
    return k, c["num_hidden_layers"] - k


def layers_by_kind(c: dict) -> tuple:
    """(window layers, full layers)."""
    n = sum(t == SLIDING for t in c["layer_types"])
    return n, len(c["layer_types"]) - n


def n_params(c: dict) -> int:
    d = c["hidden_size"]
    dense, sparse = n_layers(c)
    return int(dense * dense_layer_params(c) + sparse * sparse_layer_params(c)
               + 2 * c["vocab_size"] * d + d)


def weight_bytes(c: dict, dtype: str = "bfloat16") -> int:
    return n_params(c) * BYTES[dtype]


def kv_row_bytes(c: dict, dtype: str = "bfloat16") -> int:
    """K and V of one token in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BYTES[dtype]


def kv_tokens_kept(c: dict, context: float) -> tuple:
    """Tokens of a sequence ``context`` long whose rows a layer of each kind
    keeps: (a window layer, a full layer)."""
    return min(context, c["sliding_window"]), context


def kv_bytes_per_token(c: dict, context: float = None,
                       dtype: str = "bfloat16") -> float:
    """Page bytes a token of a sequence ``context`` tokens long takes over
    all layers BY LAYER TYPE: a full layer keeps every token, a window layer
    the last ``sliding_window``.  Without a context: what pools of one
    shape would hold, every layer every token (10,240 at five layers)."""
    win, full = layers_by_kind(c)
    if context is None:
        return (win + full) * kv_row_bytes(c, dtype)
    kept_w, kept_f = kv_tokens_kept(c, context)
    return (win * kept_w + full * kept_f) * kv_row_bytes(c, dtype) / context


# --------------------------------------------------------------------------
# required operations and bytes

def expected_experts_hit(c: dict, assignments: float) -> float:
    """Experts of one layer that at least one of ``assignments`` (token,
    expert) pairs reaches, the router taken as uniform (seeded weights)."""
    e = c["num_experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** assignments)


def expert_bytes_per_call(c: dict, rows: float, experts_hit: float,
                          dtype: str = "bfloat16") -> float:
    """HBM bytes ONE call of the grouped product (one layer) has to move
    for ``rows`` tokens: the three matrices of the ``experts_hit`` experts
    some token reaches, once, plus each assignment's input and output row."""
    rows_io = 2 * rows * c["num_experts_per_tok"] * c["hidden_size"]
    return (experts_hit * expert_params(c) + rows_io) * BYTES[dtype]


def paged_attend_bytes(c: dict, window_rows: float, full_rows: float,
                       queries: float, dtype: str = "bfloat16") -> float:
    """HBM bytes the paged decode kernel has to move in ONE decode step,
    all layers: ``window_rows`` token rows (K and V) inside the bounds in
    each window layer, ``full_rows`` in each full layer, plus a query in
    and an output out for every head of ``queries`` live slots a layer."""
    win, full = layers_by_kind(c)
    q_and_o = 2 * queries * c["num_attention_heads"] * c["head_dim"]
    return ((win * window_rows + full * full_rows) * kv_row_bytes(c, dtype)
            + (win + full) * q_and_o * BYTES[dtype])


def decode_step_bytes(c: dict, slots: float, contexts,
                      experts_hit: float = None,
                      dtype: str = "bfloat16") -> float:
    """HBM bytes one decode step has to read: every layer's attention
    weights, the dense layers' MLPs, the sparse layers' routers, shared
    experts and the routed experts ``slots`` tokens reach (``experts_hit``
    a layer, else what uniform routing gives), the output head, and the K
    and V rows of ``contexts`` (the live sequences' lengths, or their sum
    with ``slots`` of equal length) that each kind of layer keeps."""
    dense, sparse = n_layers(c)
    if experts_hit is None:
        experts_hit = expected_experts_hit(
            c, slots * c["num_experts_per_tok"])
    if not hasattr(contexts, "__len__"):
        contexts = [contexts / max(slots, 1)] * int(round(slots))
    d = c["hidden_size"]
    weights = (dense * dense_layer_params(c)
               + sparse * sparse_layer_params(c, experts_hit)
               + c["vocab_size"] * d + d)
    kept = [kv_tokens_kept(c, n) for n in contexts]
    return weights * BYTES[dtype] + paged_attend_bytes(
        c, sum(w for w, _ in kept), sum(f for _, f in kept), len(kept),
        dtype)


def active_matmul_params(c: dict) -> float:
    """Parameters ONE token is multiplied with, all layers (no head)."""
    d = c["hidden_size"]
    dense, sparse = n_layers(c)
    attn = attention_params_per_layer(c) - 2 * c["head_dim"]
    return (dense * (attn + 3 * d * c["intermediate_size"])
            + sparse * (attn + d * c["num_experts"] + shared_params(c)
                        + c["num_experts_per_tok"] * expert_params(c)))


def attention_flops(c: dict, q_len: int, kv_len: int) -> float:
    """QK^T and PV, all layers, the queries being the last ``q_len`` of
    ``kv_len`` keys (causal) and, in a window layer, each seeing at most the
    last ``sliding_window`` keys."""
    H, hd, W = c["num_attention_heads"], c["head_dim"], c["sliding_window"]
    win, full = layers_by_kind(c)
    first = kv_len - q_len  # keys before the first query
    causal = q_len * first + q_len * (q_len + 1) / 2
    # a query at position p sees min(p + 1, W) keys
    lo, hi = first + 1, kv_len  # keys the first and the last query would see
    if hi <= W:
        bounded = causal
    elif lo >= W:
        bounded = q_len * W
    else:
        n = W - lo  # queries that still see all their keys
        bounded = n * (lo + W - 1) / 2 + (q_len - n) * W
    return 4.0 * H * hd * (full * causal + win * bounded)


def prefill_flops(c: dict, new_tokens: int, cached_tokens: int = 0) -> float:
    """Forward pass over ``new_tokens`` prompt tokens behind
    ``cached_tokens`` resident ones (an earlier chunk's), and the head for
    the last token."""
    return (2.0 * new_tokens * active_matmul_params(c)
            + attention_flops(c, new_tokens, cached_tokens + new_tokens)
            + 2.0 * c["hidden_size"] * c["vocab_size"])


# --------------------------------------------------------------------------
# what the program is given (imports the program; runs in the chip's holder)

def model_module():
    from ray_tpu.models import afmoe

    return afmoe


def model_config(c: dict, **overrides):
    af = model_module()  # a program without this family fails here
    n = c["num_hidden_layers"]
    # (a cut of the cut keeps the pattern's END, its full layer)
    types = tuple(c["layer_types"])[-n:]
    return af.AfmoeConfig(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=n,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], n_dense_layers=c["num_dense_layers"],
        d_expert=c["moe_intermediate_size"], n_experts=c["num_experts"],
        experts_per_token=c["num_experts_per_tok"],
        n_shared_experts=c["num_shared_experts"],
        norm_topk_prob=bool(c["route_norm"]),
        routed_scaling_factor=float(c["route_scale"]), layer_types=types,
        sliding_window=c["sliding_window"],
        mup_enabled=bool(c["mup_enabled"]),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype=c.get("dtype", "bfloat16")), **overrides})


def make_params(c: dict, seed: int, dtype: str):
    """Seeded weights in the type they are served in, made on the device in
    one jitted call (``rbg`` keys, as ``llama_dense.make_params``); the
    model's own ``init`` draws and casts the experts a layer at a time and
    draws the non-zero ``router_bias`` (sd ``expert_bias_sd``)."""
    import jax
    import jax.numpy as jnp

    af, cfg = model_module(), model_config(c)
    sd = float(c.get("expert_bias_sd", 0.05))
    return jax.jit(lambda k: af.init(cfg, k, jnp.dtype(dtype), sd))(
        jax.random.key(seed, impl="rbg"))


def cut_mantissa(x, bits: int = 3):
    """x with its mantissa cut to ``bits`` (a planted fault's precision;
    ``reduce_precision``: XLA would remove a pair of casts)."""
    import jax
    import jax.numpy as jnp

    return jax.lax.reduce_precision(x.astype(jnp.float32), 8,
                                    bits).astype(x.dtype)


def pinned_logits(c: dict, params, tokens, rows, weights, chosen,
                  fault: str = None):
    """The PROGRAM's layers (its gated attention with the QK norm, rotation
    by layer type and window mask; its dropless ``dispatch``, grouped kernel
    and shared expert; its sandwich norms, embedding factor and head) over
    tokens [b, s] with the sparse layers' routing HANDED IN: weights and
    experts [sparse layers, b * s, k], the reference's.  Returns logits
    [b, r, vocab] float32 at ``rows`` [b, r].

    Why the routing is pinned (``families/sdar_moe.py`` has SDAR's numbers,
    the same 8 of 128): a bf16 stream moves a router score a little, the
    8th and 9th of 128 lie close, so the served model takes another 8th
    expert than float32 in a share of tokens a layer, each swap moving the
    logits by more than a misread weight would.  That is no fault, and it
    buries what IS one unless both sides take the same experts.  Attention
    is the cacheless ``afmoe.batch_attend``; the pages, the kernel's bound,
    the chunks and the engine are checked on the rows they leave and on
    tokens (runners/serve_windowed).

    ``fault`` (a control's, ``runners/serve_windowed.py`` ``control``; never
    set in a run): ``window_short``
    the window a page shorter; ``full_rotated`` the full layers' q and k
    rotated too; ``no_gate`` attention's gate left out; ``experts_3bit``
    the experts' weights cut to 3 bits of mantissa."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, moe

    af, cfg = model_module(), model_config(c)
    if fault == "no_gate":
        # the gate's product zeroed: sigmoid(0) is the same for every
        # value, and the norm behind W_o takes the constant out
        params = {**params, **{part: {**params[part], "attn": {
            **params[part]["attn"], "wg": 0 * params[part]["attn"]["wg"]}}
            for part in ("dense", "layers")}}
    params = af.serving_layout(params)  # the products as they are served
    positions = jnp.arange(tokens.shape[1])
    window = cfg.sliding_window - (
        c["engine"]["page_size"] if fault == "window_short" else 0)
    attends = {"full": af.batch_attend(cfg, positions),
               "window": af.batch_attend(cfg, positions, window)}
    experts = params["layers"]["experts"]
    if fault == "experts_3bit":
        experts = jax.tree.map(cut_mantissa, experts)
    kinds = cfg.kinds()

    def body(x, p, li, ffn):
        kind, _ = kinds[li]
        if "router" in p:  # a sparse layer: the routing is the reference's
            i = li - cfg.n_dense_layers

            def ffn(p, h):
                hf = h.reshape(-1, h.shape[-1])
                out, _ = moe.dispatch(hf, weights[i], chosen[i], experts, i)
                return (out + moe.shared_mlp(p["shared"], hf)).reshape(
                    h.shape)

        return af.layer(cfg, p, x, positions[None, :], attends[kind], None,
                        ffn, rotate=kind == "window"
                        or fault == "full_rotated")[0]

    x, _ = af.walk_layers(cfg, params, body,
                          llama.embed(params, tokens, cfg))
    x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return llama.head(params, x, cfg)


def engine_rows(engine, pages: list, wpages: list):
    """The K and V rows the engine's TWO pools hold for one sequence: each
    [layers, len(pages) x page_size, kv heads, head_dim], a full layer's
    through ``pages`` and a window layer's through ``wpages`` (null entries
    read the null page: whoever compares leaves those positions out)."""
    import jax.numpy as jnp

    cfg = engine.model_cfg
    idx = {"full": jnp.asarray(pages, jnp.int32),
           "window": jnp.asarray(wpages, jnp.int32)}

    def rows(pool):
        return jnp.stack([
            pool[kind][i][idx[kind]].reshape(-1, cfg.n_kv_heads,
                                             cfg.head_dim)
            for kind, i in cfg.kinds()])

    return rows(engine.cache_k), rows(engine.cache_v)
