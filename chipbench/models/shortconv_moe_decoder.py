"""A sparse decoder of gated short convolutions and grouped-query attention as
LFM2-8B-A1B's public ``config.json`` describes it (``model_type: lfm2_moe``):
layers of two kinds in a fixed pattern (``layer_types``: ``conv`` and
``full_attention``), ``num_dense_layers`` leading blocks with a dense SwiGLU,
then blocks of ``num_experts`` routed experts chosen by biased sigmoid scores,
no shared expert, the head tied to the embedding.  This file is the
benchmark's own copy of the architecture: the weights it makes from the seed,
the plain float32 reference (no cache, no kernel: the conv as shifted sums over
the whole sequence, attention by the full causal score matrix, the experts by
a mask one at a time) and the sizes the per-layer readers count from.  It
reads the published keys of the configuration file and imports nothing of the
program under test.

The equations (``d`` = hidden size, RMSNorm eps ``norm_eps``, no bias anywhere).
``RMSNorm_n(x) = x / sqrt(mean_n(x^2) + eps) * w``.  Pre-norm blocks: ``h = x +
Op(RMSNorm(x))``, ``out = h + FFN(RMSNorm(h))``; after the last block one more
RMSNorm (hf: ``embedding_norm``), then the head, which is the embedding
transposed.

*Gated short convolution* (``conv`` layers; ``K = conv_L_cache``)::

    [B | C | u]_t = W_in x_t                       d -> 3 d
    v_t = B_t * u_t
    c_t = sum_{j < K} w[:, j] * v_{t - (K - 1) + j}    depthwise, causal, v_s = 0 for s < 0,
                                                       no bias, no activation
    y_t = W_out (C_t * c_t)
    a cache keeps v_{t-1} ... v_{t-(K-1)}

*Attention* (``full_attention`` layers; ``nh`` heads over ``ng`` KV heads of
``hs`` = hidden / nh)::

    q = RMSNorm_hs(W_q x) g_q,  k = RMSNorm_hs(W_k x) g_k     a head at a time, one weight of
                                                              hs a layer each, before the rotation
    rotary on all hs dims, theta ``rope_theta``, half-split pairs
    y = W_o softmax_causal(q k^T hs^-1/2) v                  nh / ng query heads a KV head, no window

*Dense FFN* (layers ``< num_dense_layers``): ``W_2 (silu(W_1 h) * W_3 h)``, width
``intermediate_size``.  *Expert FFN* (the others)::

    s = sigmoid(W_g h)                 over all E experts, in float32
    chosen = top-k of (s + b)          b (E,) float32 a layer (``use_expert_bias``), no gradient
    w_i = s_i / (sum_chosen s + 1e-6) * routed_scaling_factor      (``norm_topk_prob``)
    y = sum_{i in chosen} w_i E_i(h)   E_i a SwiGLU of width ``moe_intermediate_size``

The bias moves the choice and never a weight.  Departures from hf's
``modeling_lfm2_moe.py``, each noted in the configuration's ``assumed``: the
router's product runs in float32 here and in the program (hf runs it at the
model's dtype); ``b`` is drawn from the seed, not zero.

Weight layout (what the program is handed; a linear layer is ``x @ W.T`` unless
said otherwise): ``wte (V, d)`` (the head too), ``ln_f (d)``; a block:
``norm_1``, ``norm_2 (d)``; a conv block: ``conv.in_proj (3 d, d)`` packed ``[B |
C | u]``, ``conv.conv_w (d, K)``, ``conv.out_proj (d, d)``; an attention block:
``attn.wq (nh hs, d)``, ``attn.wk/wv (ng hs, d)``, ``attn.wo (d, nh hs)``,
``attn.q_norm``, ``attn.k_norm (hs)``; a dense layer's ``mlp.fc_1/fc_2 (I, d)``
(gate, up), ``mlp.proj (d, I)``; an expert layer's ``mlp.gate (E, d)``,
``mlp.expert_bias (E)`` float32, ``mlp.fc_1/fc_2 (held d, Im)`` and ``mlp.proj
(held Im, d)`` (the held experts stacked, ``x @ W`` layout: expert ``e`` is rows
``[e d, (e + 1) d)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.common import seed_key

OUTSIDE_BLOCKS = 0xFFFF   # the place folded into the key of the embedding
ROWS = 256                # rows of an expert and of a dense FFN at a time
COLUMNS = 1792            # hidden columns of a dense SwiGLU whose weights are upcast at a time
BIAS_STD = 0.1            # of the router's bias: the scores' own spread with weights of std 0.02


def sizes(hf: dict) -> dict:
    nh, L = hf["num_attention_heads"], hf["num_hidden_layers"]
    kinds = tuple(hf["layer_types"][:L])      # the published pattern, as deep as this cut
    E = hf.get("published_num_experts", hf["num_experts"])
    return {"C": hf["hidden_size"], "nh": nh, "ng": hf["num_key_value_heads"],
            "hs": hf.get("head_dim") or hf["hidden_size"] // nh,
            "I": hf["intermediate_size"], "Im": hf["moe_intermediate_size"], "V": hf["vocab_size"],
            "L": L, "kinds": kinds, "L_attn": kinds.count("full_attention"), "L_conv": kinds.count("conv"),
            "K": hf["conv_L_cache"], "E": E, "held": hf["num_experts"], "first": hf.get("expert_first", 0),
            "k": hf["num_experts_per_tok"], "route_scale": float(hf["routed_scaling_factor"]),
            "dense": hf["num_dense_layers"], "theta": float(hf["rope_theta"]),
            "eps": hf["norm_eps"], "std": hf["initializer_range"]}


def program_config(hf: dict) -> dict:
    """The published keys under the names ``thunder_tpu.models.llama.Config``
    gives them."""
    s = sizes(hf)
    return dict(name=hf["model_name"], block_size=hf["max_position_embeddings"], vocab_size=s["V"],
                n_layer=s["L"], n_head=s["nh"], n_embd=s["C"], head_size=s["hs"], n_query_groups=s["ng"],
                norm_eps=s["eps"], rope_base=int(s["theta"]), tie_embeddings=True, qk_norm=True,
                layer_types=s["kinds"], conv_kernel=s["K"],
                mlp_class="SparseMoE", intermediate_size=s["Im"], n_expert=s["E"], n_expert_per_token=s["k"],
                expert_first=s["first"], expert_held=s["held"], moe_router="sigmoid_bias",
                routed_scaling_factor=s["route_scale"], first_k_dense=s["dense"], dense_intermediate_size=s["I"])


def expert_params(hf: dict) -> int:
    s = sizes(hf)
    return 3 * s["C"] * s["Im"]


def layer_params(hf: dict, i: int) -> int:
    s = sizes(hf)
    if s["kinds"][i] == "conv":
        mixer = 3 * s["C"] * s["C"] + s["C"] * s["K"] + s["C"] * s["C"]
    else:
        mixer = s["C"] * s["hs"] * (2 * s["nh"] + 2 * s["ng"]) + 2 * s["hs"]
    ffn = 3 * s["C"] * s["I"] if i < s["dense"] else s["E"] * s["C"] + s["E"] + s["held"] * expert_params(hf)
    return mixer + ffn + 2 * s["C"]


def param_count(hf: dict) -> int:
    """The tied embedding counted once."""
    s = sizes(hf)
    return sum(layer_params(hf, i) for i in range(s["L"])) + s["V"] * s["C"] + s["C"]


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """What the paged cache keeps a token: K and V of the attention layers."""
    s = sizes(hf)
    return 2 * s["L_attn"] * s["ng"] * s["hs"] * itemsize


def tail_bytes_per_slot(hf: dict, itemsize: int = 2) -> int:
    """What a request keeps beside its K/V: the conv layers' last ``K - 1`` inputs."""
    s = sizes(hf)
    return s["L_conv"] * (s["K"] - 1) * s["C"] * itemsize


def make_params(hf: dict, seed_words, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed (``common.seed_words``, an argument of the
    caller's ``jit``, not a constant of it), made where that ``jit`` puts
    them.  Each leaf's key is folded from its place in the model, so layer
    ``i`` is the same whatever the depth, and a held expert from its number
    among all the experts, so expert ``e`` is the same whatever the share.
    Matrices normal of std ``initializer_range``; norms 1; the router's bias
    normal of std ``BIAS_STD`` in float32 (hf starts it at zero, where it would
    test nothing: the scores of random weights spread by about as much, so a
    third of the choices move)."""
    s = sizes(hf)
    root = seed_key(seed_words)
    key = lambda tag, layer: jax.random.fold_in(jax.random.fold_in(root, layer), tag)  # noqa: E731

    def dense(tag: int, layer: int, fan_out: int, fan_in: int):
        return (jax.random.normal(key(tag, layer), (fan_out, fan_in), jnp.float32) * s["std"]).astype(dtype)

    def experts(tag: int, layer: int, fan_in: int, fan_out: int):
        """The held experts' matrices, ``x @ W`` layout, stacked on rows: ``(held fan_in, fan_out)``."""
        def one(e):
            w = jax.random.normal(jax.random.fold_in(key(tag, layer), e), (fan_in, fan_out), jnp.float32)
            return (w * s["std"]).astype(dtype)

        return jax.lax.map(one, s["first"] + jnp.arange(s["held"])).reshape(s["held"] * fan_in, fan_out)

    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    blocks = []
    for i, kind in enumerate(s["kinds"]):
        block = {"norm_1": ones(s["C"]), "norm_2": ones(s["C"])}
        if kind == "conv":
            block["conv"] = {"in_proj": dense(0, i, 3 * s["C"], s["C"]), "conv_w": dense(1, i, s["C"], s["K"]),
                             "out_proj": dense(2, i, s["C"], s["C"])}
        else:
            block["attn"] = {"wq": dense(0, i, s["nh"] * s["hs"], s["C"]), "wk": dense(1, i, s["ng"] * s["hs"], s["C"]),
                             "wv": dense(2, i, s["ng"] * s["hs"], s["C"]), "wo": dense(3, i, s["C"], s["nh"] * s["hs"]),
                             "q_norm": ones(s["hs"]), "k_norm": ones(s["hs"])}
        if i < s["dense"]:
            block["mlp"] = {"fc_1": dense(5, i, s["I"], s["C"]), "fc_2": dense(6, i, s["I"], s["C"]),
                            "proj": dense(7, i, s["C"], s["I"])}
        else:
            block["mlp"] = {"gate": dense(8, i, s["E"], s["C"]),
                            "expert_bias": jax.random.normal(key(12, i), (s["E"],), jnp.float32) * BIAS_STD,
                            "fc_1": experts(9, i, s["C"], s["Im"]), "fc_2": experts(10, i, s["C"], s["Im"]),
                            "proj": experts(11, i, s["Im"], s["C"])}
        blocks.append(block)
    return {"wte": dense(0, OUTSIDE_BLOCKS, s["V"], s["C"]), "blocks": blocks, "ln_f": ones(s["C"])}


# --------------------------------------------------------------------------
# The plain reference: float32, highest matmul precision, no kernel, no cache
# --------------------------------------------------------------------------

def _lin(x, w):
    return x @ w.astype(jnp.float32).T


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def rope_tables(s: dict, seq_len: int):
    """cos and sin ``(T, hs)`` in float32, rotate-half layout."""
    inv = s["theta"] ** (-jnp.arange(0, s["hs"], 2, dtype=jnp.float32) / s["hs"])
    ang = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1) * sin


def _by_rows(fn, x, *rest):
    """``fn(rows, *rest)`` over blocks of ``ROWS`` rows of ``x``, one block
    after another: the intermediates of one block at a time."""
    T = x.shape[0]
    n = ROWS if T % ROWS == 0 else T
    out = jax.lax.map(lambda a: fn(a, *rest), x.reshape(T // n, n, *x.shape[1:]))
    return out.reshape(T, *out.shape[2:])


def gated_inputs(x, cp):
    """``v = B * u`` and the gate ``C`` of every token, ``(T, d)`` each."""
    b, c, u = jnp.split(_lin(x, cp["in_proj"]), 3, axis=-1)
    return b * u, c


def _short_conv(x, cp, s):
    T, K = x.shape[0], s["K"]
    v, c = gated_inputs(x, cp)
    padded = jnp.pad(v, ((K - 1, 0), (0, 0)))
    w = cp["conv_w"].astype(jnp.float32)
    return _lin(c * sum(padded[j:j + T] * w[:, j] for j in range(K)), cp["out_proj"])


def keys_values(x, ap, cos, sin, s):
    """What a cache holds of every token: the normed, rotated keys and the
    values, ``(T, ng, hs)`` each."""
    T = x.shape[0]
    k = _rms(_lin(x, ap["wk"]).reshape(T, s["ng"], s["hs"]), ap["k_norm"], s["eps"])
    return _rotate(k, cos[:, None], sin[:, None]), _lin(x, ap["wv"]).reshape(T, s["ng"], s["hs"])


def _attention(x, ap, cos, sin, s):
    """The full causal score matrix, the query heads of one KV head at a time."""
    T, nh, ng, hs = x.shape[0], s["nh"], s["ng"], s["hs"]
    q = _rms(_lin(x, ap["wq"]).reshape(T, nh, hs), ap["q_norm"], s["eps"])
    q = _rotate(q, cos[:, None], sin[:, None]).reshape(T, ng, nh // ng, hs)
    k, v = keys_values(x, ap, cos, sin, s)
    keep = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def group(a):
        qg, kg, vg = a                                         # (T, rep, hs), (T, hs), (T, hs)
        sc = jnp.where(keep[None], jnp.einsum("trh,sh->rts", qg, kg) * hs ** -0.5, -jnp.inf)
        return jnp.einsum("rts,sh->trh", jax.nn.softmax(sc, axis=-1), vg)

    o = jax.lax.map(group, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1)))   # (ng, T, rep, hs)
    return _lin(o.swapaxes(0, 1).reshape(T, nh * hs), ap["wo"])


def _swiglu(h, mp):
    """``W_2 (silu(W_1 h) * W_3 h)``, ``COLUMNS`` hidden columns at a time: the
    float32 copy of that many columns' weights, and no more."""
    I = mp["fc_1"].shape[0]
    n = COLUMNS if I % COLUMNS == 0 else I
    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731

    def columns(y, c):
        w1, w2 = (jax.lax.dynamic_slice_in_dim(mp[k], c * n, n, axis=0) for k in ("fc_1", "fc_2"))
        w3 = jax.lax.dynamic_slice_in_dim(mp["proj"], c * n, n, axis=1)
        return y + (jax.nn.silu(h @ f32(w1).T) * (h @ f32(w2).T)) @ f32(w3).T, None

    return jax.lax.scan(columns, jnp.zeros_like(h), jnp.arange(I // n))[0]


def route(h, gate, bias, s):
    """The biased choice: ``(weights (T, k), experts (T, k))`` over all E."""
    sc = jax.nn.sigmoid(_lin(h, gate))
    _, idx = jax.lax.top_k(sc + bias.astype(jnp.float32), s["k"])
    w = jnp.take_along_axis(sc, idx, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * s["route_scale"], idx


def _expert_share(h, mp, s):
    """The held experts by a mask: every token through every held expert, one
    expert at a time, weighted by what the router gave it (zero if not chosen)."""
    C, Im, held = s["C"], s["Im"], s["held"]
    w, idx = route(h, mp["gate"], mp["expert_bias"], s)
    fc_1, fc_2, proj = (mp["fc_1"].reshape(held, C, Im), mp["fc_2"].reshape(held, C, Im),
                        mp["proj"].reshape(held, Im, C))

    def one(y, a):
        e, w1, w2, w3 = a
        we = jnp.sum(jnp.where(idx == s["first"] + e, w, 0.0), axis=-1)                  # (T,)
        f32 = lambda m: m.astype(jnp.float32)  # noqa: E731
        rows = lambda hb: (jax.nn.silu(hb @ f32(w1)) * (hb @ f32(w2))) @ f32(w3)  # noqa: E731
        return y + we[:, None] * _by_rows(rows, h), None

    return jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(held), fc_1, fc_2, proj))[0]


def _mixer(n1, bp, cos, sin, s, kind):
    return _short_conv(n1, bp["conv"], s) if kind == "conv" else _attention(n1, bp["attn"], cos, sin, s)


def _block(x, bp, cos, sin, s, kind, dense):
    """One block on ``x (T, d)`` float32."""
    h = x + _mixer(_rms(x, bp["norm_1"], s["eps"]), bp, cos, sin, s, kind)
    n2 = _rms(h, bp["norm_2"], s["eps"])
    return h + (_swiglu(n2, bp["mlp"]) if dense else _expert_share(n2, bp["mlp"], s))


def _hf_items(hf: dict) -> tuple:
    keep = lambda v: tuple(v) if isinstance(v, list) and all(isinstance(e, str) for e in v) else v  # noqa: E731
    return tuple(sorted((k, keep(v)) for k, v in hf.items() if not isinstance(keep(v), (dict, list))))


@functools.partial(jax.jit, static_argnames=("hf_items", "kind", "dense"))
def _ref_block(x, bp, *, hf_items, kind, dense):
    with jax.default_matmul_precision("highest"):
        s = sizes(dict(hf_items))
        cos, sin = rope_tables(s, x.shape[0])
        return _block(x, bp, cos, sin, s, kind, dense)


@functools.partial(jax.jit, static_argnames=("hf_items", "kind"))
def _ref_held(x, bp, n_real, *, hf_items, kind):
    """What a cache holds of this layer once the first ``n_real`` tokens of
    ``x`` went in: a conv layer's tail ``(K - 1, d)`` (``v`` of tokens ``n_real
    - (K - 1) ... n_real - 1``, zeros before the first), or every token's key
    and value ``(ng, T, hs)``."""
    with jax.default_matmul_precision("highest"):
        s = sizes(dict(hf_items))
        n1 = _rms(x, bp["norm_1"], s["eps"])
        if kind == "conv":
            v, _ = gated_inputs(n1, bp["conv"])
            return jax.lax.dynamic_slice_in_dim(jnp.pad(v, ((s["K"] - 1, 0), (0, 0))), n_real, s["K"] - 1, axis=0)
        cos, sin = rope_tables(s, x.shape[0])
        return tuple(a.swapaxes(0, 1) for a in keys_values(n1, bp["attn"], cos, sin, s))


@functools.partial(jax.jit, static_argnames=("eps",))
def _ref_logits(x, ln_f, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, ln_f, eps) @ head.astype(jnp.float32).T


def _layers(hf: dict, params: dict):
    s = sizes(hf)
    return [(bp, kind, i < s["dense"]) for i, (bp, kind) in enumerate(zip(params["blocks"], s["kinds"]))]


def ref_hidden(hf: dict, params: dict, tokens):
    """Final hidden states ``(T, d)`` before the last norm, for one sequence
    of token ids ``(T,)``.  One block's weights are upcast at a time, an
    expert's inside it."""
    items = _hf_items(hf)
    x = params["wte"][tokens].astype(jnp.float32)
    for bp, kind, dense in _layers(hf, params):
        x = _ref_block(x, bp, hf_items=items, kind=kind, dense=dense)
    return x


def ref_caches(hf: dict, params: dict, tokens, n_real: int):
    """What a server's caches hold of one sequence after its first ``n_real``
    tokens (the rest of ``tokens`` is padding), a layer at a time, in the
    model's order: ``("conv", tail (K - 1, d))`` for a conv layer, ``("kv", (k,
    v))``, each ``(ng, n_real, hs)``, for a full_attention layer, all float32.
    A generator: one layer's arrays live at a time."""
    items = _hf_items(hf)
    x = params["wte"][tokens].astype(jnp.float32)
    for bp, kind, dense in _layers(hf, params):
        got = _ref_held(x, bp, n_real, hf_items=items, kind=kind)
        yield ("conv", got) if kind == "conv" else ("kv", tuple(a[:, :n_real] for a in got))
        del got
        x = _ref_block(x, bp, hf_items=items, kind=kind, dense=dense)


def ref_logits(hf: dict, params: dict, tokens, positions):
    """Logits ``(len(positions), V)`` of the full forward pass at the given
    positions of one sequence; the head is the embedding."""
    x = ref_hidden(hf, params, tokens)
    return _ref_logits(x[positions], params["ln_f"], params["wte"], eps=hf["norm_eps"])
