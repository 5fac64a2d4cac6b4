"""A dense decoder-only transformer as its paper and its public ``config.json``
describe it: RMSNorm before each sub-layer, grouped-query attention with
rotary embedding (rotate-half) and an optional sliding window, SwiGLU
feed-forward, untied head.  This file is the benchmark's own copy of the
architecture: the weights it makes from the seed, the plain float32
reference, and the count of operations a token needs.  It reads the
published keys of the configuration file and imports nothing of the
program under test.

Weight layout (what the program is handed): ``wte (V, C)``, ``lm_head
(V, C)``, ``ln_f (C)`` and per block ``norm_1``, ``norm_2`` ``(C)``,
``attn.wq (nh*hs, C)``, ``attn.wk/wv (ng*hs, C)``, ``attn.wo (C, nh*hs)``,
``mlp.fc_1/fc_2 (I, C)`` (gate, up) and ``mlp.proj (C, I)``; a linear layer
is ``x @ W.T``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.common import seed_key

OUTSIDE_BLOCKS = 0xFFFF   # the place folded into the keys of the embedding and the head

def sizes(hf: dict) -> dict:
    nh = hf["num_attention_heads"]
    return {"C": hf["hidden_size"], "nh": nh, "ng": hf["num_key_value_heads"],
            "hs": hf.get("head_dim") or hf["hidden_size"] // nh,
            "I": hf["intermediate_size"], "V": hf["vocab_size"],
            "L": hf["num_hidden_layers"], "W": hf.get("sliding_window"),
            "theta": hf.get("rope_theta", 10000.0), "eps": hf["rms_norm_eps"],
            "std": hf["initializer_range"]}


def program_config(hf: dict) -> dict:
    """The published keys under the names ``thunder_tpu.models.llama.Config``
    gives them."""
    s = sizes(hf)
    return dict(name=hf["model_name"], block_size=hf["max_position_embeddings"],
                vocab_size=s["V"], n_layer=s["L"], n_head=s["nh"], n_embd=s["C"],
                head_size=s["hs"], n_query_groups=s["ng"], intermediate_size=s["I"],
                sliding_window=s["W"], rope_base=int(s["theta"]), norm_eps=s["eps"])


def layer_params(hf: dict) -> int:
    s = sizes(hf)
    attn = s["C"] * s["hs"] * (2 * s["nh"] + 2 * s["ng"])
    return attn + 3 * s["C"] * s["I"] + 2 * s["C"]


def param_count(hf: dict) -> int:
    s = sizes(hf)
    return s["L"] * layer_params(hf) + 2 * s["V"] * s["C"] + s["C"]


def make_params(hf: dict, seed_words, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed (``common.seed_words``, an argument of the
    caller's ``jit``, not a constant of it), made where that ``jit`` puts
    them.  Each leaf's key is folded from its place in the model, so layer
    ``i`` is the same whatever the depth."""
    s = sizes(hf)
    root = seed_key(seed_words)

    def dense(tag: int, layer: int, fan_out: int, fan_in: int):
        k = jax.random.fold_in(jax.random.fold_in(root, layer), tag)
        return (jax.random.normal(k, (fan_out, fan_in), jnp.float32) * s["std"]).astype(dtype)

    ones = lambda: jnp.ones((s["C"],), dtype)  # noqa: E731
    blocks = []
    for i in range(s["L"]):
        blocks.append({
            "norm_1": ones(),
            "attn": {"wq": dense(0, i, s["nh"] * s["hs"], s["C"]),
                     "wk": dense(1, i, s["ng"] * s["hs"], s["C"]),
                     "wv": dense(2, i, s["ng"] * s["hs"], s["C"]),
                     "wo": dense(3, i, s["C"], s["nh"] * s["hs"])},
            "norm_2": ones(),
            "mlp": {"fc_1": dense(4, i, s["I"], s["C"]),
                    "fc_2": dense(5, i, s["I"], s["C"]),
                    "proj": dense(6, i, s["C"], s["I"])},
        })
    return {"wte": dense(0, OUTSIDE_BLOCKS, s["V"], s["C"]), "blocks": blocks,
            "ln_f": ones(), "lm_head": dense(1, OUTSIDE_BLOCKS, s["V"], s["C"])}


def rope_tables(hf: dict, seq_len: int):
    """cos and sin ``(T, hs)`` in float32, rotate-half layout."""
    s = sizes(hf)
    inv = 1.0 / (s["theta"] ** (jnp.arange(0, s["hs"], 2, dtype=jnp.float32) / s["hs"]))
    ang = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


# --------------------------------------------------------------------------
# Operations a token needs (forward and backward; recomputation not counted)
# --------------------------------------------------------------------------

def attended_keys(seq_len: int, window: int | None) -> float:
    """Mean number of keys a query attends in a causal sequence."""
    if window is None or window >= seq_len:
        return (seq_len + 1) / 2.0
    full = seq_len - window            # queries that see a whole window
    return (window * (window + 1) / 2.0 + full * window) / seq_len


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """6 per parameter of the matrices a token passes through (the embedding
    is a lookup), plus attention's two products over the keys it attends:
    2*2*nh*hs per key forward, twice that backward."""
    s = sizes(hf)
    matmul_params = s["L"] * (layer_params(hf) - 2 * s["C"]) + s["V"] * s["C"]
    attn = s["L"] * 4 * s["nh"] * s["hs"] * attended_keys(seq_len, s["W"])
    return 6.0 * matmul_params + 3.0 * attn


# --------------------------------------------------------------------------
# The plain reference: float32, highest matmul precision, no kernel, no cache
# --------------------------------------------------------------------------

def _fp8(x):
    """Rounds to float8 e4m3 with one scale per tensor: the control's
    arithmetic, the nearest precision below bfloat16."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 448.0 / amax
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


@jax.custom_vjp
def _lin_fp8(x, w):
    """``x @ w.T`` as a float8 recipe computes it: both operands of the
    forward product and of the two backward products rounded to float8."""
    return _fp8(x) @ _fp8(w).T


def _lin_fp8_fwd(x, w):
    return _lin_fp8(x, w), (x, w)


def _lin_fp8_bwd(res, g):
    x, w = res
    g = _fp8(g)
    return g @ _fp8(w), g.T @ _fp8(x)


_lin_fp8.defvjp(_lin_fp8_fwd, _lin_fp8_bwd)


def _lin(x, w, q):
    w = w.astype(jnp.float32)
    return _lin_fp8(x, w) if q else x @ w.T


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1) * sin


ROWS = 512


def _by_rows(fn, x, *rest):
    """``fn(rows, first_row, *rest)`` over blocks of ``ROWS`` rows of ``x``, one
    block after another and each under ``jax.checkpoint``: a backward pass
    computes a block's intermediates again, and holds those of one block at a
    time."""
    T = x.shape[0]
    n = ROWS if T % ROWS == 0 else T
    blocks = x.reshape(T // n, n, *x.shape[1:])
    out = jax.lax.map(lambda a: jax.checkpoint(fn)(a[0], a[1], *rest),
                      (blocks, jnp.arange(T // n) * n))
    return out.reshape(T, *out.shape[2:])


def _attend(qb, q0, k, v, window):
    """Softmax attention of a block of queries ``(t, ng, rep, hs)``, the first
    at position ``q0``, over all keys."""
    kpos = jnp.arange(k.shape[0])
    qpos = q0 + jnp.arange(qb.shape[0])
    sc = jnp.einsum("tgrh,sgh->grts", qb, k) / (qb.shape[-1] ** 0.5)
    keep = kpos[None, :] <= qpos[:, None]
    if window is not None:
        keep &= kpos[None, :] > qpos[:, None] - window
    sc = jnp.where(keep[None, None], sc, -jnp.inf)
    return jnp.einsum("grts,sgh->tgrh", jax.nn.softmax(sc, axis=-1), v)


def _mlp(h, _, mp, quant):
    return _lin(jax.nn.silu(_lin(h, mp["fc_1"], quant)) * _lin(h, mp["fc_2"], quant),
                mp["proj"], quant)


def _block(x, bp, cos, sin, s, quant):
    """One block on ``x (T, C)`` float32."""
    T = x.shape[0]
    h = _rms(x, bp["norm_1"], s["eps"])
    q = _lin(h, bp["attn"]["wq"], quant).reshape(T, s["nh"], s["hs"])
    k = _lin(h, bp["attn"]["wk"], quant).reshape(T, s["ng"], s["hs"])
    v = _lin(h, bp["attn"]["wv"], quant).reshape(T, s["ng"], s["hs"])
    q = _rotate(q, cos[:, None], sin[:, None])
    k = _rotate(k, cos[:, None], sin[:, None])
    q = q.reshape(T, s["ng"], s["nh"] // s["ng"], s["hs"])
    y = _by_rows(functools.partial(_attend, window=s["W"]), q, k, v)
    x = x + _lin(y.reshape(T, s["nh"] * s["hs"]), bp["attn"]["wo"], quant)
    return x + _by_rows(functools.partial(_mlp, quant=quant), _rms(x, bp["norm_2"], s["eps"]),
                        bp["mlp"])


@functools.partial(jax.jit, static_argnames=("hf_items", "quant"))
def _ref_block(x, bp, cos, sin, *, hf_items, quant):
    with jax.default_matmul_precision("highest"):
        return _block(x, bp, cos, sin, sizes(dict(hf_items)), quant)


@functools.partial(jax.jit, static_argnames=("hf_items", "quant"))
def _ref_block_grads(x, bp, cos, sin, dy, *, hf_items, quant):
    """The gradient of the loss in one block's input and in its weights
    (float32), from the gradient ``dy`` in its output."""
    s = sizes(dict(hf_items))
    with jax.default_matmul_precision("highest"):
        bp = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), bp)
        _, vjp = jax.vjp(lambda x_, p_: _block(x_, p_, cos, sin, s, quant), x, bp)
        return vjp(dy)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ref_logits(x, ln_f, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, ln_f, eps) @ head.astype(jnp.float32).T


def _hf_items(hf: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in hf.items() if not isinstance(v, (dict, list))))


def ref_hidden(hf: dict, params: dict, tokens, *, quant: bool = False, keep: list | None = None):
    """Final hidden states ``(T, C)`` before the last norm, for one sequence
    of token ids ``(T,)``.  One block's weights are upcast at a time.  Every
    block's input is appended to ``keep``, where one is given."""
    items = _hf_items(hf)
    cos, sin = rope_tables(hf, tokens.shape[0])
    x = params["wte"][tokens].astype(jnp.float32)
    for bp in params["blocks"]:
        if keep is not None:
            keep.append(x)
        x = _ref_block(x, bp, cos, sin, hf_items=items, quant=quant)
    return x


def ref_logits(hf: dict, params: dict, tokens, positions, *, quant: bool = False):
    """Logits ``(len(positions), V)`` of the full forward pass at the given
    positions of one sequence."""
    x = ref_hidden(hf, params, tokens, quant=quant)
    return _ref_logits(x[positions], params["ln_f"], params["lm_head"], eps=hf["rms_norm_eps"])


HEAD_CHUNK = 2048


@functools.partial(jax.jit, static_argnames=("eps",))
def _ref_head_chunk(x, ln_f, head, targets, scale, *, eps):
    """Summed cross-entropy of a chunk of positions, and the gradient of
    ``scale`` times that sum in the chunk's hidden states, the last norm and
    the head."""
    def ce(x_, ln_f_, head_):
        lg = _rms(x_, ln_f_, eps) @ head_.T
        picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked)

    with jax.default_matmul_precision("highest"):
        total, grads = jax.value_and_grad(lambda *a: scale * ce(*a), argnums=(0, 1, 2))(
            x, ln_f.astype(jnp.float32), head.astype(jnp.float32))
        return total / scale, grads


@functools.partial(jax.jit, static_argnames=("rows",))
def _scatter_rows(dx, tokens, *, rows):
    return jax.ops.segment_sum(dx, tokens, num_segments=rows)


def ref_loss_and_grads(hf: dict, params: dict, idx, targets, *, quant: bool = False):
    """The mean next-token cross-entropy over a batch ``(B, T)``, and an
    iterator over its gradient in every weight, by a plain backward pass in
    float32.  The iterator gives one part of the weights' tree at a time, as
    ``(where, part)``, so that the whole gradient never exists at once:
    ``((), {"ln_f", "lm_head"})``, then ``(("blocks", i), block)`` from the last
    block to the first, then ``((), {"wte"})``.  With ``quant`` the linear
    layers of the blocks compute in float8, forward and backward (the
    control)."""
    items, eps = _hf_items(hf), hf["rms_norm_eps"]
    B, T = idx.shape
    cos, sin = rope_tables(hf, T)
    add = lambda a, b: b if a is None else jax.tree_util.tree_map(jnp.add, a, b)  # noqa: E731
    inputs, dys, total, head_grads = [], [], 0.0, None
    for b in range(B):
        kept: list = []
        x = ref_hidden(hf, params, idx[b], quant=quant, keep=kept)
        inputs.append(kept)
        dx = []
        for p0 in range(0, T, HEAD_CHUNK):
            ce, (g_x, g_ln, g_head) = _ref_head_chunk(
                x[p0:p0 + HEAD_CHUNK], params["ln_f"], params["lm_head"],
                targets[b, p0:p0 + HEAD_CHUNK], 1.0 / (B * T), eps=eps)
            total += float(ce)
            dx.append(g_x)
            head_grads = add(head_grads, {"ln_f": g_ln, "lm_head": g_head})
        dys.append(jnp.concatenate(dx, axis=0))

    def grads():
        yield (), head_grads
        for i in reversed(range(len(params["blocks"]))):
            g_block = None
            for b in range(B):
                dys[b], g = _ref_block_grads(inputs[b].pop(), params["blocks"][i], cos, sin, dys[b],
                                             hf_items=items, quant=quant)
                g_block = add(g_block, g)
            yield ("blocks", i), g_block
        g_wte = None
        for b in range(B):
            g_wte = add(g_wte, _scatter_rows(dys[b], idx[b], rows=params["wte"].shape[0]))
        yield (), {"wte": g_wte}

    return total / (B * T), grads()
