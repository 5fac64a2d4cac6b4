"""A sparse decoder whose router reads the block's input ahead of attention, as
SmallThinker-21BA3B-Instruct's public ``config.json`` describes it
(``model_type: smallthinker``, 21B-A3B; arXiv:2507.20984): grouped-query attention
layers of two kinds in a fixed pattern (``sliding_window_layout``: 0 a global layer
over every key, 1 a window layer over the last ``sliding_window_size`` keys;
``rope_layout``: 1 a layer that rotates its q and k, 0 one that rotates nothing),
and in every block ``moe_num_primary_experts`` whole gated-ReLU experts chosen by a
softmax router **that reads the block's input**, before the first norm and before
attention, while the experts read the second norm of the stream after attention.
No shared expert, no dense layer, no bias, no q/k norm; an untied head.  This file
is the benchmark's own copy of the architecture: the weights it makes from the
seed, the plain float32 reference (no cache, no kernel: attention by the full
causal score matrix with the window as a mask, ``QUERY_ROWS`` queries at a time so
that a prompt of 10,240 tokens fits; the experts by a mask one at a time) and the
sizes the per-layer readers count from.  It reads the published keys of the
configuration file and imports nothing of the program under test.

The equations (``C`` = hidden size 2560; ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``,
eps ``rms_norm_eps``)::

    h0 = E[ids]
    layer l on x (T, C):
    r   = float32(x) Wr^T                                    Wr (E, C), no bias: the block's INPUT
    p   = softmax(r) over all E                              ``moe_primary_router_apply_softmax``
    idx = top k of p;  w = p[idx] / sum(p[idx])              ``norm_topk_prob``
    a   = n1(x)
    q   = split_nh(a Wq);  k = split_ng(a Wk);  v = split_ng(a Wv)
    ``rope_layout[l]`` 1:            q, k <- rope(q, k; theta ``rope_theta``, all hs dims, half-split pairs)
    ``sliding_window_layout[l]`` 1:  key j seen by query i iff i - W < j <= i       (W = ``sliding_window_size``)
    ``sliding_window_layout[l]`` 0:  key j seen iff j <= i
    o   = softmax(q k^T / sqrt(hs)) v                        query head h reads KV head h // (nh / ng): 7 a KV head
    x'  = x + concat_heads(o) Wo
    h   = n2(x')
    m   = sum_j w_j (relu(h W1[idx_j]) * (h W3[idx_j])) W2[idx_j]        ``moe_ffn_hidden_size`` wide each (ReGLU)
    out = x' + m
    logits = nf(x_L) W_head

The published layouts put the global, unrotated layer first in every period of four
(``l % 4 == 0``) and rotate exactly the window layers.  A cache keeps, of a window
layer, the rotated keys and the values of the last ``W`` tokens, and of a global
layer the keys and the values of every token.

What the config's keys do not show is listed under ``assumed`` in the
configuration's file: that the router's input is the block's input *before*
``n1`` (the family's modelling code and llama.cpp's ``smallthinker`` graph; one
line of ``_block`` holds the choice for the reference, ``moe_route_block_input``
for the program), no bias anywhere, no q/k norm, the rotation's pairing, the
window's edge.  Departures from the published code, each noted there too: the
router's product runs in float32 here and in the program; norm weights are 1.

The chip's share of a deployment: all ``moe_num_primary_experts`` of each layer it
holds (``expert_held == n_expert``), the whole vocabulary; the cut is in depth
alone.  ``sizes`` still gives ``held`` and ``first`` so that the joined readers and
the share test (``tests/test_prerouted_serving.py``: 2 of 8 held, four shares) read
them: the router scores all the experts and keeps its ``k``; a share computes the
held experts' part of the sum and what the absent ones would add is left out.

Weight layout (what the program is handed; a linear layer is ``x @ W.T`` unless
said otherwise): ``wte (V, C)``, ``lm_head (V, C)``, ``ln_f (C)``; a block: ``norm_1``,
``norm_2 (C)``; ``attn.wq (nh hs, C)``, ``attn.wk/wv (ng hs, C)``, ``attn.wo (C, nh hs)``;
``mlp.gate (E, C)``, ``mlp.fc_1/fc_2 (held C, Im)`` (gate, up) and ``mlp.proj (held
Im, C)`` (the held experts stacked, ``x @ W`` layout: expert ``e`` is rows ``[e C,
(e + 1) C)``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.common import seed_key

OUTSIDE_BLOCKS = 0xFFFF   # the place folded into the key of the embedding and of the head
ROWS = 256                # rows of an expert at a time
HEAD_ROWS = 8192          # rows of the head whose float32 copy is made at a time, the most
QUERY_ROWS = 512          # queries whose score matrices (a KV head's) live at once
KINDS = {0: "full_attention", 1: "sliding_attention"}       # ``sliding_window_layout``'s entries


def sizes(hf: dict) -> dict:
    nh, L = hf["num_attention_heads"], hf["num_hidden_layers"]
    kinds = tuple(KINDS[w] for w in hf["sliding_window_layout"][:L])      # the published pattern, as deep as this cut
    rope_kinds = {k for k, r in zip(kinds, hf["rope_layout"]) if r}
    # one rule a kind: the program rotates by kind (``rope_kinds``), and so does the published layout
    assert all((k in rope_kinds) == bool(r) for k, r in zip(kinds, hf["rope_layout"])), hf["rope_layout"]
    assert hf["moe_primary_router_apply_softmax"] and hf["norm_topk_prob"] and not hf["tie_word_embeddings"]
    assert hf.get("rope_scaling") is None
    E = hf["moe_num_primary_experts"]
    return {"C": hf["hidden_size"], "nh": nh, "ng": hf["num_key_value_heads"], "hs": hf["head_dim"],
            "Im": hf["moe_ffn_hidden_size"], "V": hf["vocab_size"], "L": L, "kinds": kinds,
            "rope_kinds": tuple(sorted(rope_kinds)), "L_swa": kinds.count("sliding_attention"),
            "L_walk_full": kinds.count("full_attention"), "W": hf["sliding_window_size"],
            "E": E, "held": hf.get("expert_held", E), "first": hf.get("expert_first", 0),
            "k": hf["moe_num_active_primary_experts"], "dense": 0, "theta": float(hf["rope_theta"]),
            "eps": hf["rms_norm_eps"], "std": hf["initializer_range"]}


def program_config(hf: dict) -> dict:
    """The published keys under the names ``thunder_tpu.models.llama.Config``
    gives them."""
    s = sizes(hf)
    return dict(name=hf["model_name"], block_size=hf["max_position_embeddings"], vocab_size=s["V"],
                padded_vocab_size=s["V"], n_layer=s["L"], n_head=s["nh"], n_embd=s["C"], head_size=s["hs"],
                n_query_groups=s["ng"], norm_eps=s["eps"], rope_base=int(s["theta"]), tie_embeddings=False,
                layer_types=s["kinds"], layer_window=s["W"], rope_kinds=s["rope_kinds"],
                mlp_class="SparseMoE", intermediate_size=s["Im"], n_expert=s["E"], n_expert_per_token=s["k"],
                expert_first=s["first"], expert_held=s["held"], moe_router="softmax", moe_activation="reglu",
                moe_route_block_input=True)


def expert_params(hf: dict) -> int:
    s = sizes(hf)
    return 3 * s["C"] * s["Im"]


def layer_params(hf: dict, i: int, held: int | None = None) -> int:
    """Layer ``i``'s parameters with ``held`` routed experts (this share's, where None)."""
    s = sizes(hf)
    mixer = s["C"] * s["hs"] * (2 * s["nh"] + 2 * s["ng"])        # Wq, Wo; Wk, Wv
    return mixer + s["E"] * s["C"] + (s["held"] if held is None else held) * expert_params(hf) + 2 * s["C"]


def param_count(hf: dict, held: int | None = None, vocab: int | None = None) -> int:
    """Embedding and head apart; ``held`` experts a layer and ``vocab`` rows (this share's, where None)."""
    s = sizes(hf)
    return (sum(layer_params(hf, i, held) for i in range(s["L"]))
            + 2 * (s["V"] if vocab is None else vocab) * s["C"] + s["C"])


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """What the paged cache keeps a token of a request's whole length: K and V of the global layers."""
    s = sizes(hf)
    return 2 * s["L_walk_full"] * s["ng"] * s["hs"] * itemsize


def ring_bytes_per_slot(hf: dict, block_size: int = 16, itemsize: int = 2) -> int:
    """What a request keeps of its window layers, whatever its length: a ring of the window and a block."""
    s = sizes(hf)
    return s["L_swa"] * (-(-s["W"] // block_size) + 1) * block_size * 2 * s["ng"] * s["hs"] * itemsize


def make_params(hf: dict, seed_words, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed (``common.seed_words``, an argument of the
    caller's ``jit``, not a constant of it), made where that ``jit`` puts them.
    Each leaf's key is folded from its place in the model, so layer ``i`` is the
    same whatever the depth, and a held expert from its number among all the
    experts, so expert ``e`` is the same whatever the share.  Matrices normal of
    std ``initializer_range``; norms 1."""
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
        blocks.append({
            "norm_1": ones(C), "norm_2": ones(C),
            "attn": {"wq": dense(0, i, nh * hs, C), "wk": dense(1, i, ng * hs, C), "wv": dense(2, i, ng * hs, C),
                     "wo": dense(3, i, C, nh * hs)},
            "mlp": {"gate": dense(8, i, s["E"], C), "fc_1": experts(9, i, C, s["Im"]),
                    "fc_2": experts(10, i, C, s["Im"]), "proj": experts(11, i, s["Im"], C)}})
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


def keys_values(a, ap, s, kind):
    """What a cache holds of every token: the keys, rotated where the kind
    rotates, and the values, ``(T, ng, hs)`` each."""
    T = a.shape[0]
    k = _lin(a, ap["wk"]).reshape(T, s["ng"], s["hs"])
    if kind in s["rope_kinds"]:
        cos, sin = rope_tables(s, T)
        k = _rotate(k, cos[:, None], sin[:, None])
    return k, _lin(a, ap["wv"]).reshape(T, s["ng"], s["hs"])


def _attention(a, ap, s, kind):
    """The full causal score matrix (the window a mask on it), the query heads of
    one KV head at a time, ``QUERY_ROWS`` queries at a time; then ``Wo``."""
    T, nh, ng, hs = a.shape[0], s["nh"], s["ng"], s["hs"]
    q = _lin(a, ap["wq"]).reshape(T, nh, hs)
    if kind in s["rope_kinds"]:
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
    return _lin(o, ap["wo"])


def route(r_in, gate, s):
    """The softmax choice from what the router reads: ``(weights (T, k), experts (T, k))`` over all E."""
    w, idx = jax.lax.top_k(jax.nn.softmax(_lin(r_in, gate), axis=-1), s["k"])
    return w / jnp.sum(w, axis=-1, keepdims=True), idx


def routed_share(h, r_in, mp, s):
    """The held experts' part of the routed sum, by a mask: every token's ``h``
    through every held expert, one expert at a time, weighted by what the router
    gave it on ``r_in`` (zero if not chosen)."""
    C, Im, held = s["C"], s["Im"], s["held"]
    w, idx = route(r_in, mp["gate"], s)
    fc_1, fc_2, proj = (mp["fc_1"].reshape(held, C, Im), mp["fc_2"].reshape(held, C, Im),
                        mp["proj"].reshape(held, Im, C))

    def one(y, a):
        e, w1, w2, w3 = a
        we = jnp.sum(jnp.where(idx == s["first"] + e, w, 0.0), axis=-1)                  # (T,)
        rows = lambda hb: (jax.nn.relu(hb @ _f32(w1)) * (hb @ _f32(w2))) @ _f32(w3)  # noqa: E731
        return y + we[:, None] * _by_rows(rows, h), None

    return jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(held), fc_1, fc_2, proj))[0]


def _block(x, bp, s, kind):
    """One block on ``x (T, C)`` float32."""
    x1 = x + _attention(_rms(x, bp["norm_1"], s["eps"]), bp["attn"], s, kind)
    # the router reads ``x``, the block's input (``assumed.router_input``); the experts read n2(x')
    return x1 + routed_share(_rms(x1, bp["norm_2"], s["eps"]), x, bp["mlp"], s)


def _hf_items(hf: dict) -> tuple:
    keep = lambda v: tuple(v) if isinstance(v, list) and all(isinstance(e, (str, int)) for e in v) else v  # noqa: E731
    return tuple(sorted((k, keep(v)) for k, v in hf.items() if not isinstance(keep(v), (dict, list))))


def _sizes_of(hf_items: tuple) -> dict:
    return sizes({k: list(v) if isinstance(v, tuple) else v for k, v in hf_items})


@functools.partial(jax.jit, static_argnames=("hf_items", "kind"))
def _ref_block(x, bp, *, hf_items, kind):
    with jax.default_matmul_precision("highest"):
        return _block(x, bp, _sizes_of(hf_items), kind)


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
    for kind, bp in zip(s["kinds"], params["blocks"]):
        if visit is not None:
            visit(kind, x, bp)
        x = _ref_block(x, bp, hf_items=items, kind=kind)
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
    positions of one sequence."""
    x = ref_hidden(hf, params, tokens)
    return _ref_logits(x[positions], params["ln_f"], params["lm_head"], eps=hf["rms_norm_eps"])
