"""A looped dense decoder as Ouro-2.6B's public ``config.json`` describes it
(``model_type: ouro``; the family's paper is "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741): a stack of ``num_hidden_layers`` blocks with a
norm before *and* after each sublayer, run ``total_ut_steps`` times over one set of
weights, the last norm closing every pass, an exit gate on each pass's closed
state, and a cache a pass.  This file is the benchmark's own copy of the
architecture: the weights it makes from the seed, the plain float32 reference (two
nested Python loops, no scan, no kernel, no cache: attention by the full causal
score matrix, queries in blocks) and the sizes the per-layer readers count from.
It reads the published keys of the configuration file and imports nothing of the
program under test.

The equations (``C`` = hidden size 2048; every norm an RMSNorm with a weight, eps
``rms_norm_eps``: ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``)::

    block l on x (T, C):
    a  = n1(x)                                              input_layernorm
    q  = split_nh(a Wq);  k = split_ng(a Wk);  v = split_ng(a Wv)      no bias, no q/k norm
    q, k <- rope(q, k; theta ``rope_theta``, all hs dims, half-split pairs)
    o  = softmax(q k^T / sqrt(hs), causal) v                query head h reads KV head h // (nh / ng)
    x' = x + n1post(concat_heads(o) Wo)                     input_layernorm_2
    m  = W_down (silu(W_gate n2(x')) * (W_up n2(x')))       post_attention_layernorm; C -> I -> C
    out = x' + n2post(m)                                    post_attention_layernorm_2

    h_0 = E[ids]
    for t in 0 .. total_ut_steps - 1:                       the SAME blocks, the same weights, every pass
        u = h_t
        for l in 0 .. L - 1:
            u = block_l(u)        its keys and values are slab  s = t * L + l : a cache A PASS
        h_{t+1} = nf(u)           the final norm closes EVERY pass; its output opens the next
        g_t     = w_g . h_{t+1} + b_g                       the exit gate, Linear(C, 1), with a bias
    lambda_t = sigmoid(g_t);  p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < last);  p_last = prod_j (1 - lambda_j)
    e = first t with p_0 + ... + p_t >= ``early_exit_threshold``, else the last pass       (a token's own e)
    logits = h_{e+1} W_head^T

Every pass runs whatever ``e`` is: later tokens attend this token's keys of every
pass.  A token's keys of pass ``t`` attend the context's keys of pass ``t`` only,
which a full forward over the whole sequence does by construction.

The config's keys show the widths, the depth, the pass count and the threshold.
They do not show the four norms' places, the final norm inside the loop, the
gate's form or the exit rule: those are the family's published modelling code (hf
``modeling_ouro.py``) and the paper, as ISSUE 62's author recalls them (no network
here, and ``transformers`` 4.57.6 on this machine does not carry the model), each
listed under ``assumed`` in the configuration's file.  Departures from that code,
noted there too: norm weights are 1; the gate's weight is drawn like every other
(N(0, ``initializer_range``^2)) and its bias is 0.

Weight layout (what the program is handed; a linear layer is ``x @ W.T``): ``wte (V,
C)``, ``lm_head (V, C)``, ``ln_f (C)``, ``exit_gate.w (C)``, ``exit_gate.b ()``; a block:
``norm_1`` (n1), ``norm_1_post``, ``norm_2`` (n2), ``norm_2_post``, each ``(C)``; ``attn.wq (nh
hs, C)``, ``attn.wk/wv (ng hs, C)``, ``attn.wo (C, nh hs)``; ``mlp.fc_1/fc_2 (I, C)`` (gate,
up), ``mlp.proj (C, I)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.common import seed_key

OUTSIDE_BLOCKS = 0xFFFF   # the place folded into the keys of the embedding, the head and the gate
QUERY_ROWS = 512          # queries whose score matrices live at once
HEAD_ROWS = 8192          # rows of the head whose float32 copy is made at a time, the most


def sizes(hf: dict) -> dict:
    nh, L, passes = hf["num_attention_heads"], hf["num_hidden_layers"], hf["total_ut_steps"]
    assert not hf["tie_word_embeddings"] and not hf.get("use_sliding_window") and hf.get("sliding_window") is None
    assert hf["hidden_act"] == "silu" and set(hf.get("layer_types") or ["full_attention"]) == {"full_attention"}
    return {"C": hf["hidden_size"], "nh": nh, "ng": hf["num_key_value_heads"],
            "hs": hf.get("head_dim") or hf["hidden_size"] // nh,
            "I": hf["intermediate_size"], "V": hf["vocab_size"], "L": L, "passes": passes,
            # the K/V layer-walks a decode step makes: every layer of every pass
            "L_full": L * passes, "threshold": float(hf["early_exit_threshold"]),
            "theta": float(hf["rope_theta"]), "eps": hf["rms_norm_eps"], "std": hf["initializer_range"]}


def slab(hf: dict, t: int, l: int) -> int:
    """Where a server keeps layer ``l``'s keys and values of pass ``t``: a cache a pass,
    the passes one after another (``assumed.cache``)."""
    return t * hf["num_hidden_layers"] + l


def program_config(hf: dict) -> dict:
    """The published keys under the names ``thunder_tpu.models.llama.Config``
    gives them."""
    s = sizes(hf)
    return dict(name=hf["model_name"], block_size=hf["max_position_embeddings"], vocab_size=s["V"],
                padded_vocab_size=s["V"], n_layer=s["L"], n_head=s["nh"], n_embd=s["C"], head_size=s["hs"],
                n_query_groups=s["ng"], intermediate_size=s["I"], norm_eps=s["eps"], rope_base=int(s["theta"]),
                tie_embeddings=False, sandwich_norm=True, n_pass=s["passes"], exit_threshold=s["threshold"])


def layer_params(hf: dict) -> int:
    s = sizes(hf)
    return s["C"] * s["hs"] * (2 * s["nh"] + 2 * s["ng"]) + 3 * s["C"] * s["I"] + 4 * s["C"]


def param_count(hf: dict) -> int:
    s = sizes(hf)
    return s["L"] * layer_params(hf) + 2 * s["V"] * s["C"] + 2 * s["C"] + 1


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    s = sizes(hf)
    return s["L_full"] * 2 * s["ng"] * s["hs"] * itemsize


def make_params(hf: dict, seed_words, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed (``common.seed_words``, an argument of the
    caller's ``jit``), made where that ``jit`` puts them.  Each leaf's key is
    folded from its place in the model, so layer ``i`` is the same whatever the
    depth and the pass count."""
    s = sizes(hf)
    root = seed_key(seed_words)

    def dense(tag: int, layer: int, fan_out: int, fan_in: int):
        k = jax.random.fold_in(jax.random.fold_in(root, layer), tag)
        return (jax.random.normal(k, (fan_out, fan_in), jnp.float32) * s["std"]).astype(dtype)

    ones = lambda: jnp.ones((s["C"],), dtype)  # noqa: E731
    blocks = [{"norm_1": ones(), "norm_1_post": ones(), "norm_2": ones(), "norm_2_post": ones(),
               "attn": {"wq": dense(0, i, s["nh"] * s["hs"], s["C"]), "wk": dense(1, i, s["ng"] * s["hs"], s["C"]),
                        "wv": dense(2, i, s["ng"] * s["hs"], s["C"]), "wo": dense(3, i, s["C"], s["nh"] * s["hs"])},
               "mlp": {"fc_1": dense(4, i, s["I"], s["C"]), "fc_2": dense(5, i, s["I"], s["C"]),
                       "proj": dense(6, i, s["C"], s["I"])}}
              for i in range(s["L"])]
    return {"wte": dense(0, OUTSIDE_BLOCKS, s["V"], s["C"]), "blocks": blocks, "ln_f": ones(),
            "exit_gate": {"w": dense(2, OUTSIDE_BLOCKS, 1, s["C"])[0], "b": jnp.zeros((), dtype)},
            "lm_head": dense(1, OUTSIDE_BLOCKS, s["V"], s["C"])}


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
    inv = 1.0 / (s["theta"] ** (jnp.arange(0, s["hs"], 2, dtype=jnp.float32) / s["hs"]))
    ang = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1) * sin


def keys_values(a, ap, s):
    """A layer's rotated keys and its values ``(T, ng, hs)`` from its normed input ``a (T, C)``."""
    T = a.shape[0]
    cos, sin = rope_tables(s, T)
    k = _rotate(_lin(a, ap["wk"]).reshape(T, s["ng"], s["hs"]), cos[:, None], sin[:, None])
    return k, _lin(a, ap["wv"]).reshape(T, s["ng"], s["hs"])


def _attention(a, ap, s):
    T = a.shape[0]
    cos, sin = rope_tables(s, T)
    q = _rotate(_lin(a, ap["wq"]).reshape(T, s["nh"], s["hs"]), cos[:, None], sin[:, None])
    q = q.reshape(T, s["ng"], s["nh"] // s["ng"], s["hs"])
    k, v = keys_values(a, ap, s)
    kpos = jnp.arange(T)

    def attend(args):       # a block of queries, the first at position q0, over all keys
        qb, q0 = args
        sc = jnp.einsum("tgrh,sgh->grts", qb, k) / (s["hs"] ** 0.5)
        keep = kpos[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None]
        return jnp.einsum("grts,sgh->tgrh", jax.nn.softmax(jnp.where(keep[None, None], sc, -jnp.inf), axis=-1), v)

    n = QUERY_ROWS if T % QUERY_ROWS == 0 else T
    y = jax.lax.map(attend, (q.reshape(T // n, n, *q.shape[1:]), jnp.arange(T // n) * n))
    return _lin(y.reshape(T, s["nh"] * s["hs"]), ap["wo"])


def _block(x, bp, s):
    """One block on ``x (T, C)`` float32."""
    a = _rms(x, bp["norm_1"], s["eps"])
    x = x + _rms(_attention(a, bp["attn"], s), bp["norm_1_post"], s["eps"])
    n = _rms(x, bp["norm_2"], s["eps"])
    m = _lin(jax.nn.silu(_lin(n, bp["mlp"]["fc_1"])) * _lin(n, bp["mlp"]["fc_2"]), bp["mlp"]["proj"])
    return x + _rms(m, bp["norm_2_post"], s["eps"])


def _hf_items(hf: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in hf.items() if not isinstance(v, (dict, list))))


def _sizes_of(hf_items: tuple) -> dict:
    return sizes(dict(hf_items))


@functools.partial(jax.jit, static_argnames=("hf_items",))
def _ref_block(x, bp, *, hf_items):
    with jax.default_matmul_precision("highest"):
        return _block(x, bp, _sizes_of(hf_items))


@functools.partial(jax.jit, static_argnames=("hf_items",))
def _ref_held(x, bp, *, hf_items):
    """What a cache holds of this layer in this pass: every token's key and value ``(ng, T, hs)``."""
    with jax.default_matmul_precision("highest"):
        s = _sizes_of(hf_items)
        return tuple(a.swapaxes(0, 1) for a in keys_values(_rms(x, bp["norm_1"], s["eps"]), bp["attn"], s))


@functools.partial(jax.jit, static_argnames=("eps",))
def _ref_close(u, ln_f, gate, *, eps):
    """The norm that closes a pass, and the exit gate's logit of every token on the closed state."""
    with jax.default_matmul_precision("highest"):
        h = _rms(u, ln_f, eps)
        return h, h @ _f32(gate["w"]) + _f32(gate["b"])


def exit_pass(gates, threshold: float):
    """The exit rule on the gates' logits ``(passes, T)``: the pass each token's head reads
    ``(T,)`` and the exit probabilities ``(passes, T)``."""
    lam = jax.nn.sigmoid(gates)
    p, stay = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    p = jnp.stack(p + [stay])
    reached = jnp.cumsum(p, axis=0) >= threshold
    return jnp.where(jnp.any(reached, axis=0), jnp.argmax(reached, axis=0), lam.shape[0] - 1), p


@jax.jit
def _ref_head(x, head):
    """The head, a whole number of its rows at a time, ``HEAD_ROWS`` the most."""
    with jax.default_matmul_precision("highest"):
        V = head.shape[0]
        rows = max(r for r in range(1, min(V, HEAD_ROWS) + 1) if V % r == 0)
        out = jax.lax.map(lambda i: x @ _f32(jax.lax.dynamic_slice_in_dim(head, i * rows, rows, axis=0)).T,
                          jnp.arange(V // rows))                                           # (V / rows, T, rows)
        return out.swapaxes(0, 1).reshape(x.shape[0], V)


def _walk(hf: dict, params: dict, tokens, visit=None):
    """The passes in order on one sequence, the blocks in order inside each: two
    Python loops.  ``visit(t, l, x before the block, block)`` before each block.
    Returns every pass's closed state ``(passes, T, C)`` and gate logits ``(passes, T)``."""
    s, items = sizes(hf), _hf_items(hf)
    h = params["wte"][tokens].astype(jnp.float32)
    closed, gates = [], []
    for t in range(s["passes"]):
        u = h
        for l, bp in enumerate(params["blocks"]):
            if visit is not None:
                visit(t, l, u, bp)
            u = _ref_block(u, bp, hf_items=items)
            jax.block_until_ready(u)      # a block at a time on the device too
        h, g = _ref_close(u, params["ln_f"], params["exit_gate"], eps=s["eps"])
        closed.append(h)
        gates.append(g)
    return jnp.stack(closed), jnp.stack(gates)


def ref_exit(hf: dict, params: dict, tokens):
    """The pass the exit rule picks for every token ``(T,)`` and the exit probabilities ``(passes, T)``."""
    _, gates = _walk(hf, params, tokens)
    return exit_pass(gates, sizes(hf)["threshold"])


def ref_hidden(hf: dict, params: dict, tokens):
    """What the head reads ``(T, C)``: a token's closed state of the pass its exit rule picks."""
    closed, gates = _walk(hf, params, tokens)
    e, _ = exit_pass(gates, sizes(hf)["threshold"])
    return jnp.take_along_axis(closed, e[None, :, None], axis=0)[0]


def ref_caches(hf: dict, params: dict, tokens, n_real: int, slabs=None):
    """What a server's caches hold of one sequence after its first ``n_real`` tokens
    (the rest of ``tokens`` is padding), for the named slabs ``[(t, l), ...]`` (every
    one where ``slabs`` is None), float32: ``[((t, l), (k, v))]``, each ``(ng, n_real, hs)``,
    in the order of the walk."""
    items, out = _hf_items(hf), []
    want = None if slabs is None else {tuple(x) for x in slabs}

    def visit(t, l, x, bp):
        if want is None or (t, l) in want:
            got = jax.device_get(_ref_held(x, bp, hf_items=items))
            out.append(((t, l), tuple(a[:, :n_real] for a in got)))

    _walk(hf, params, tokens, visit)
    return out


def ref_logits(hf: dict, params: dict, tokens, positions):
    """Logits ``(len(positions), V)`` of the full forward pass at the given
    positions of one sequence."""
    return _ref_head(ref_hidden(hf, params, tokens)[positions], params["lm_head"])
