"""A sparse decoder of window and global softmax attention as Trinity-Mini's
public ``config.json`` describes it (``model_type: afmoe``, 26B-A3B): grouped-query
attention layers of two kinds in a fixed pattern (``layer_types``: three
``sliding_attention`` over the last ``sliding_window`` keys to one
``full_attention`` over every key), every one of them with a norm a head on q and
k and a sigmoid gate on its output, a rotation in the window layers alone, a
norm before *and* after each sublayer, ``num_dense_layers`` leading blocks with a
dense SwiGLU, then blocks of ``num_experts`` routed experts chosen by biased
sigmoid scores beside one shared expert, an embedding scaled by ``sqrt(hidden)``
and an untied head.  This file is the benchmark's own copy of the architecture:
the weights it makes from the seed, the plain float32 reference (no cache, no
kernel: attention by the full causal score matrix with the window as a mask, the
experts by a mask one at a time) and the sizes the per-layer readers count from.
It reads the published keys of the configuration file and imports nothing of the
program under test.

The equations (``C`` = hidden size 2048; every norm an RMSNorm with a weight, eps
``rms_norm_eps``: ``RMSNorm_n(x) = x / sqrt(mean_n(x^2) + eps) * w``)::

    h0 = sqrt(C) * E[ids]                                   (``mup_enabled``)
    layer l:
    a  = n1(x)                                              input_layernorm
    q  = qn(split_nh(a Wq));  k = kn(split_ng(a Wk));  v = split_ng(a Wv)
                                                            qn, kn: RMSNorm over hs = 128, one weight of hs a layer each
    sliding_attention (l % 4 != 3):  q, k <- rope(q, k; theta ``rope_theta``, all hs dims, half-split pairs)
                                     key j seen by query i iff i - W < j <= i        (W = ``sliding_window``)
    full_attention    (l % 4 == 3):  no rotation at all;  key j seen iff j <= i
    o  = softmax(q k^T / sqrt(hs)) v                        query head h reads KV head h // (nh / ng)
    m  = (concat_heads(o) * sigmoid(a Wg)) Wo               Wg: C -> nh hs; the gate reads the normed input
    x  = x + n2(m)                                          post_attention_layernorm
    b  = n3(x)                                              pre_mlp_layernorm
    l <  num_dense_layers:  f = (silu(b W1) * (b W3)) W2    ``intermediate_size`` wide
    l >= num_dense_layers:  s = sigmoid(float32(b) Wr^T)    over all E experts
                            chosen = top k of (s + bias)    bias (E,) float32 a layer, no gradient
                            w_e = s_e / (sum_chosen s + 1e-6) * ``route_scale``      (``route_norm``)
                            f = sum_chosen w_e (silu(b W1e) * (b W3e)) W2e + (silu(b W1s) * (b W3s)) W2s
                                                            ``moe_intermediate_size`` wide each; the shared one ungated
    x  = x + n4(f)                                          post_mlp_layernorm
    logits = nf(x_L) W_head

The bias moves the choice and never a weight.  A cache keeps, of a window layer,
the normed and rotated keys and the values of the last ``W`` tokens, and of a
global layer the normed keys and the values of every token.

The config's keys show the window, the pattern, the router and muP.  They do not
show ``Wg``, ``qn`` / ``kn``, the missing rotation of the global layers or the four
norms: those are the family's published modelling code (hf ``modeling_afmoe.py``;
Arcee's description: gated attention, QK-norm, local/global 3:1 with no positional
embedding on the global layers, sandwich norm, sigmoid routing with a balancing
bias), listed under ``assumed`` in the configuration's file.  Departures from that
code, each noted there too: the router's product runs in float32 here and in the
program (the family runs it at the model's dtype); the bias is drawn from the
seed, N(0, ``BIAS_STD``^2), not zero; the renormalisation adds 1e-6 under the
weights' sum, as ``generate.route_sigmoid_bias`` does, where the family adds 1e-20
(under a millionth of a weight at eight scores in (0, 1)); norm weights are 1
where the family scales the post-sublayer norms by depth at initialisation.

The chip's share of a deployment (``num_experts`` held of ``published_num_experts``,
from ``expert_first``; ``vocab_size`` rows of ``published_vocab_size``): the router
scores all the published experts and keeps its ``k``; the layer computes the held
experts' part of the sum and the shared expert, and what the absent experts would
add is left out, here and in the program alike.

Weight layout (what the program is handed; a linear layer is ``x @ W.T`` unless
said otherwise): ``wte (V, C)``, ``lm_head (V, C)``, ``ln_f (C)``; a block: ``norm_1``
(n1), ``norm_1_post`` (n2), ``norm_2`` (n3), ``norm_2_post`` (n4), each ``(C)``;
``attn.wq (nh 2 hs, C)``: head ``h``'s query rows ``[2 h hs, (2 h + 1) hs)`` and its
gate rows after them (``Wq`` and ``Wg`` interleaved a head, one product),
``attn.wk/wv (ng hs, C)``, ``attn.wo (C, nh hs)``, ``attn.q_norm``, ``attn.k_norm (hs)``;
a dense layer's ``mlp.fc_1/fc_2 (I, C)`` (gate, up), ``mlp.proj (C, I)``; an expert
layer's ``mlp.gate (E, C)``, ``mlp.expert_bias (E)`` float32, ``mlp.fc_1/fc_2 (held C,
Im)`` and ``mlp.proj (held Im, C)`` (the held experts stacked, ``x @ W`` layout:
expert ``e`` is rows ``[e C, (e + 1) C)``), ``mlp.shared.fc_1/fc_2 (Is, C)``,
``mlp.shared.proj (C, Is)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.common import seed_key

OUTSIDE_BLOCKS = 0xFFFF   # the place folded into the key of the embedding and of the head
ROWS = 256                # rows of an expert at a time
COLUMNS = 2048            # hidden columns of a dense SwiGLU whose weights are upcast at a time
HEAD_ROWS = 8192          # rows of the head whose float32 copy is made at a time, the most
QUERY_ROWS = 512          # queries whose score matrices (a KV head's) live at once
BIAS_STD = 0.01           # of the router's bias: small beside the spread of the top scores (PERF.md section 6, PR 45)


def sizes(hf: dict) -> dict:
    nh, L = hf["num_attention_heads"], hf["num_hidden_layers"]
    kinds = tuple(hf["layer_types"][:L])      # the published pattern, as deep as this cut
    assert set(kinds) <= {"sliding_attention", "full_attention"}, kinds
    assert hf["score_func"] == "sigmoid" and hf["route_norm"] and not hf["tie_word_embeddings"]
    return {"C": hf["hidden_size"], "nh": nh, "ng": hf["num_key_value_heads"],
            "hs": hf.get("head_dim") or hf["hidden_size"] // nh,
            "I": hf["intermediate_size"], "Im": hf["moe_intermediate_size"],
            "Is": hf["moe_intermediate_size"] * hf["num_shared_experts"], "V": hf["vocab_size"],
            "L": L, "kinds": kinds, "L_swa": kinds.count("sliding_attention"),
            "L_walk_full": kinds.count("full_attention"), "W": hf["sliding_window"],
            "E": hf.get("published_num_experts", hf["num_experts"]), "held": hf["num_experts"],
            "first": hf.get("expert_first", 0), "k": hf["num_experts_per_tok"],
            "route_scale": float(hf["route_scale"]), "dense": hf["num_dense_layers"],
            "theta": float(hf["rope_theta"]), "eps": hf["rms_norm_eps"], "std": hf["initializer_range"],
            "mup": bool(hf["mup_enabled"])}


def program_config(hf: dict) -> dict:
    """The published keys under the names ``thunder_tpu.models.llama.Config``
    gives them."""
    s = sizes(hf)
    return dict(name=hf["model_name"], block_size=hf["max_position_embeddings"], vocab_size=s["V"],
                padded_vocab_size=s["V"], n_layer=s["L"], n_head=s["nh"], n_embd=s["C"], head_size=s["hs"],
                n_query_groups=s["ng"], norm_eps=s["eps"], rope_base=int(s["theta"]), tie_embeddings=False,
                scale_embedding=s["mup"], qk_norm=True, attn_output_gate=True, sandwich_norm=True,
                layer_types=s["kinds"], layer_window=s["W"], rope_kinds=("sliding_attention",),
                mlp_class="SparseMoE", intermediate_size=s["Im"], n_expert=s["E"], n_expert_per_token=s["k"],
                expert_first=s["first"], expert_held=s["held"], moe_router="sigmoid_bias",
                routed_scaling_factor=s["route_scale"], first_k_dense=s["dense"], dense_intermediate_size=s["I"],
                shared_expert_size=s["Is"], shared_expert_gate=False)


def expert_params(hf: dict) -> int:
    s = sizes(hf)
    return 3 * s["C"] * s["Im"]


def layer_params(hf: dict, i: int, held: int | None = None) -> int:
    """Layer ``i``'s parameters with ``held`` routed experts (this share's, where None)."""
    s = sizes(hf)
    mixer = s["C"] * s["hs"] * (3 * s["nh"] + 2 * s["ng"]) + 2 * s["hs"]        # Wq, Wg, Wo; Wk, Wv; qn, kn
    if i < s["dense"]:
        ffn = 3 * s["C"] * s["I"]
    else:
        ffn = (s["E"] * s["C"] + s["E"] + (s["held"] if held is None else held) * expert_params(hf)
               + 3 * s["C"] * s["Is"])
    return mixer + ffn + 4 * s["C"]


def param_count(hf: dict, held: int | None = None, vocab: int | None = None) -> int:
    """Embedding and head apart; ``held`` experts a layer and ``vocab`` rows (this share's, where None)."""
    s = sizes(hf)
    return (sum(layer_params(hf, i, held) for i in range(s["L"]))
            + 2 * (s["V"] if vocab is None else vocab) * s["C"] + s["C"])


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """What the paged cache keeps a token of a request's whole length: K and V of the full_attention layers."""
    s = sizes(hf)
    return 2 * s["L_walk_full"] * s["ng"] * s["hs"] * itemsize


def ring_bytes_per_slot(hf: dict, block_size: int = 16, itemsize: int = 2) -> int:
    """What a request keeps of its sliding_attention layers, whatever its length: a ring of the window and a block."""
    s = sizes(hf)
    return s["L_swa"] * (-(-s["W"] // block_size) + 1) * block_size * 2 * s["ng"] * s["hs"] * itemsize


def make_params(hf: dict, seed_words, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed (``common.seed_words``, an argument of the
    caller's ``jit``, not a constant of it), made where that ``jit`` puts them.
    Each leaf's key is folded from its place in the model, so layer ``i`` is the
    same whatever the depth, and a held expert from its number among all the
    experts, so expert ``e`` is the same whatever the share (a row of the
    vocabulary is not: a slice draws its own rows).  Matrices normal of std
    ``initializer_range``; norms 1; the router's bias normal of std ``BIAS_STD`` in
    float32 (the published one is trained to even the load; zeros would test
    nothing)."""
    s = sizes(hf)
    C, nh, ng, hs = s["C"], s["nh"], s["ng"], s["hs"]
    root = seed_key(seed_words)
    key = lambda tag, layer: jax.random.fold_in(jax.random.fold_in(root, layer), tag)  # noqa: E731

    def dense(tag: int, layer: int, *shape):
        return (jax.random.normal(key(tag, layer), shape, jnp.float32) * s["std"]).astype(dtype)

    def experts(tag: int, layer: int, fan_in: int, fan_out: int):
        """The held experts' matrices, ``x @ W`` layout, stacked on rows: ``(held fan_in, fan_out)``."""
        def one(e):
            w = jax.random.normal(jax.random.fold_in(key(tag, layer), e), (fan_in, fan_out), jnp.float32)
            return (w * s["std"]).astype(dtype)

        return jax.lax.map(one, s["first"] + jnp.arange(s["held"])).reshape(s["held"] * fan_in, fan_out)

    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    blocks = []
    for i in range(s["L"]):
        block = {"norm_1": ones(C), "norm_1_post": ones(C), "norm_2": ones(C), "norm_2_post": ones(C)}
        block["attn"] = {"wq": dense(0, i, nh * 2 * hs, C), "wk": dense(1, i, ng * hs, C), "wv": dense(2, i, ng * hs, C),
                         "wo": dense(3, i, C, nh * hs), "q_norm": ones(hs), "k_norm": ones(hs)}
        if i < s["dense"]:
            block["mlp"] = {"fc_1": dense(5, i, s["I"], C), "fc_2": dense(6, i, s["I"], C), "proj": dense(7, i, C, s["I"])}
        else:
            block["mlp"] = {"gate": dense(8, i, s["E"], C),
                            "expert_bias": jax.random.normal(key(12, i), (s["E"],), jnp.float32) * BIAS_STD,
                            "fc_1": experts(9, i, C, s["Im"]), "fc_2": experts(10, i, C, s["Im"]),
                            "proj": experts(11, i, s["Im"], C),
                            "shared": {"fc_1": dense(13, i, s["Is"], C), "fc_2": dense(14, i, s["Is"], C),
                                       "proj": dense(15, i, C, s["Is"])}}
        blocks.append(block)
    return {"wte": dense(0, OUTSIDE_BLOCKS, s["V"], C), "lm_head": dense(1, OUTSIDE_BLOCKS, s["V"], C),
            "blocks": blocks, "ln_f": ones(C)}


# --------------------------------------------------------------------------
# The plain reference: float32, highest matmul precision, no kernel, no cache
# --------------------------------------------------------------------------

def _f32(w):
    return w.astype(jnp.float32)


def _lin(x, w):
    return x @ _f32(w).T


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


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


def query_gate_weights(ap, s):
    """``(Wq, Wg)``, ``(nh hs, C)`` each, out of the program's one matrix (a head's query rows, then its gate rows)."""
    w = ap["wq"].reshape(s["nh"], 2, s["hs"], s["C"])
    return w[:, 0].reshape(-1, s["C"]), w[:, 1].reshape(-1, s["C"])


def keys_values(a, ap, s, kind):
    """What a cache holds of every token: the normed keys, rotated in a window
    layer and not at all in a global one, and the values, ``(T, ng, hs)`` each."""
    T = a.shape[0]
    k = _rms(_lin(a, ap["wk"]).reshape(T, s["ng"], s["hs"]), ap["k_norm"], s["eps"])
    if kind == "sliding_attention":
        cos, sin = rope_tables(s, T)
        k = _rotate(k, cos[:, None], sin[:, None])
    return k, _lin(a, ap["wv"]).reshape(T, s["ng"], s["hs"])


def _attention(a, ap, s, kind):
    """The full causal score matrix (the window a mask on it), the query heads of
    one KV head at a time, ``QUERY_ROWS`` queries at a time; then the gate and ``Wo``."""
    T, nh, ng, hs = a.shape[0], s["nh"], s["ng"], s["hs"]
    wq, wg = query_gate_weights(ap, s)
    q = _rms(_lin(a, wq).reshape(T, nh, hs), ap["q_norm"], s["eps"])
    if kind == "sliding_attention":
        cos, sin = rope_tables(s, T)
        q = _rotate(q, cos[:, None], sin[:, None])
    k, v = keys_values(a, ap, s, kind)
    rows = QUERY_ROWS if T % QUERY_ROWS == 0 else T
    q = q.reshape(T // rows, rows, ng, nh // ng, hs)

    def block(b):
        qb, first = b                                                  # (rows, ng, rep, hs), the block's first position
        t = first + jnp.arange(rows)
        keep = jnp.arange(T)[None, :] <= t[:, None]
        if kind == "sliding_attention":
            keep = jnp.logical_and(keep, jnp.arange(T)[None, :] > t[:, None] - s["W"])

        def group(g):
            qg, kg, vg = g                                             # (rows, rep, hs), (T, hs), (T, hs)
            sc = jnp.where(keep[None], jnp.einsum("trh,sh->rts", qg, kg) / math.sqrt(hs), -jnp.inf)
            return jnp.einsum("rts,sh->trh", jax.nn.softmax(sc, axis=-1), vg)

        return jax.lax.map(group, (qb.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1))).swapaxes(0, 1)

    o = jax.lax.map(block, (q, jnp.arange(T // rows) * rows)).reshape(T, nh * hs)
    return _lin(o * jax.nn.sigmoid(_lin(a, wg)), ap["wo"])


def _swiglu(h, mp):
    """``W2 (silu(W1 h) * W3 h)``, ``COLUMNS`` hidden columns at a time: the
    float32 copy of that many columns' weights, and no more."""
    I = mp["fc_1"].shape[0]
    n = COLUMNS if I % COLUMNS == 0 else I

    def columns(y, c):
        w1, w2 = (jax.lax.dynamic_slice_in_dim(mp[k], c * n, n, axis=0) for k in ("fc_1", "fc_2"))
        w3 = jax.lax.dynamic_slice_in_dim(mp["proj"], c * n, n, axis=1)
        return y + (jax.nn.silu(h @ _f32(w1).T) * (h @ _f32(w2).T)) @ _f32(w3).T, None

    return jax.lax.scan(columns, jnp.zeros_like(h), jnp.arange(I // n))[0]


def route(h, gate, bias, s):
    """The biased choice: ``(weights (T, k), experts (T, k))`` over all E."""
    sc = jax.nn.sigmoid(_lin(h, gate))
    _, idx = jax.lax.top_k(sc + _f32(bias), s["k"])
    w = jnp.take_along_axis(sc, idx, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * s["route_scale"], idx


def routed_share(h, mp, s):
    """The held experts' part of the routed sum, by a mask: every token through
    every held expert, one expert at a time, weighted by what the router gave it
    (zero if not chosen)."""
    C, Im, held = s["C"], s["Im"], s["held"]
    w, idx = route(h, mp["gate"], mp["expert_bias"], s)
    fc_1, fc_2, proj = (mp["fc_1"].reshape(held, C, Im), mp["fc_2"].reshape(held, C, Im),
                        mp["proj"].reshape(held, Im, C))

    def one(y, a):
        e, w1, w2, w3 = a
        we = jnp.sum(jnp.where(idx == s["first"] + e, w, 0.0), axis=-1)                  # (T,)
        rows = lambda hb: (jax.nn.silu(hb @ _f32(w1)) * (hb @ _f32(w2))) @ _f32(w3)  # noqa: E731
        return y + we[:, None] * _by_rows(rows, h), None

    return jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(held), fc_1, fc_2, proj))[0]


def shared_expert(h, mp):
    return _swiglu(h, mp["shared"])


def _block(x, bp, s, kind, dense):
    """One block on ``x (T, C)`` float32."""
    a = _rms(x, bp["norm_1"], s["eps"])
    x = x + _rms(_attention(a, bp["attn"], s, kind), bp["norm_1_post"], s["eps"])
    b = _rms(x, bp["norm_2"], s["eps"])
    f = _swiglu(b, bp["mlp"]) if dense else routed_share(b, bp["mlp"], s) + shared_expert(b, bp["mlp"])
    return x + _rms(f, bp["norm_2_post"], s["eps"])


def _hf_items(hf: dict) -> tuple:
    keep = lambda v: tuple(v) if isinstance(v, list) and all(isinstance(e, str) for e in v) else v  # noqa: E731
    return tuple(sorted((k, keep(v)) for k, v in hf.items() if not isinstance(keep(v), (dict, list))))


def _sizes_of(hf_items: tuple) -> dict:
    return sizes({k: list(v) if isinstance(v, tuple) else v for k, v in hf_items})


@functools.partial(jax.jit, static_argnames=("hf_items", "kind", "dense"))
def _ref_block(x, bp, *, hf_items, kind, dense):
    with jax.default_matmul_precision("highest"):
        return _block(x, bp, _sizes_of(hf_items), kind, dense)


@functools.partial(jax.jit, static_argnames=("hf_items", "kind"))
def _ref_held(x, bp, *, hf_items, kind):
    """What a cache holds of this layer: every token's key and value ``(ng, T, hs)``."""
    with jax.default_matmul_precision("highest"):
        s = _sizes_of(hf_items)
        return tuple(a.swapaxes(0, 1) for a in keys_values(_rms(x, bp["norm_1"], s["eps"]), bp["attn"], s, kind))


@functools.partial(jax.jit, static_argnames=("eps",))
def _ref_logits(x, ln_f, head, *, eps):
    """The last norm and the head, a whole number of its rows at a time,
    ``HEAD_ROWS`` the most, each cut out of the head where it lies."""
    with jax.default_matmul_precision("highest"):
        x = _rms(x, ln_f, eps)
        V = head.shape[0]
        rows = max(r for r in range(1, min(V, HEAD_ROWS) + 1) if V % r == 0)
        out = jax.lax.map(lambda i: x @ _f32(jax.lax.dynamic_slice_in_dim(head, i * rows, rows, axis=0)).T,
                          jnp.arange(V // rows))                                           # (V / rows, T, rows)
        return out.swapaxes(0, 1).reshape(x.shape[0], V)


def _walk(hf: dict, params: dict, tokens, visit=None):
    """The blocks in order on one sequence; ``visit(kind, x before the block, block)`` before each."""
    s, items = sizes(hf), _hf_items(hf)
    x = params["wte"][tokens].astype(jnp.float32)
    if s["mup"]:
        x = x * math.sqrt(s["C"])
    for i, (kind, bp) in enumerate(zip(s["kinds"], params["blocks"])):
        if visit is not None:
            visit(kind, x, bp)
        x = _ref_block(x, bp, hf_items=items, kind=kind, dense=i < s["dense"])
        # a block at a time on the device too: a call's results are allocated when it is enqueued
        jax.block_until_ready(x)
    return x


def ref_hidden(hf: dict, params: dict, tokens):
    """Final hidden states ``(T, C)`` before the last norm, for one sequence of
    token ids ``(T,)``.  One block's weights are upcast at a time, an expert's
    inside it."""
    return _walk(hf, params, tokens)


def ref_caches(hf: dict, params: dict, tokens, n_real: int):
    """What a server's caches hold of one sequence after its first ``n_real``
    tokens (the rest of ``tokens`` is padding), by the kind of each layer, in the
    model's order, all float32: ``("sliding_attention", (k, v))``, each ``(ng, n,
    hs)``, the last ``n = min(n_real, W)`` tokens' in order; ``("full_attention", (k,
    v))``, each ``(ng, n_real, hs)``.  A list, made a layer at a time."""
    s, items, out = sizes(hf), _hf_items(hf), []

    def visit(kind, x, bp):
        got = jax.device_get(_ref_held(x, bp, hf_items=items, kind=kind))
        lo = max(0, n_real - s["W"]) if kind == "sliding_attention" else 0
        out.append((kind, tuple(a[:, lo:n_real] for a in got)))

    _walk(hf, params, tokens, visit)
    return out


def ref_logits(hf: dict, params: dict, tokens, positions):
    """Logits ``(len(positions), V)`` of the full forward pass at the given
    positions of one sequence, over this share's slice of the vocabulary."""
    x = ref_hidden(hf, params, tokens)
    return _ref_logits(x[positions], params["ln_f"], params["lm_head"], eps=hf["rms_norm_eps"])
