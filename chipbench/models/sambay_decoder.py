"""A decoder-hybrid-decoder ("SambaY", arXiv:2507.06607) as
Phi-4-mini-flash-reasoning's public ``config.json`` describes it (``model_type:
phi4flash``): a self-decoder of selective-scan (Mamba-1) layers alternating
with differential attention over a sliding window, one differential attention
layer over the whole context, and a cross-decoder that alternates gated memory
units (which gate the last scan's output) with cross attention (which reads
that one full layer's keys and values), the head tied to the embedding, no
positional encoding.  This file is the benchmark's own copy of the
architecture: the weights it makes from the seed, the plain float32 reference
(no cache, no kernel: the scan a token at a time over the whole sequence,
attention by the full causal score matrix a pair of KV heads at a time) and the
sizes the per-layer readers count from.  It reads the published keys of the
configuration file and imports nothing of the program under test.

The layers (``C`` = hidden size 2560, ``L`` = 32 blocks, LayerNorm with weight and
bias, eps ``layer_norm_eps``).  Block ``l``: ``h = x + mixer_l(LN(x))``, ``y = h +
MLP(LN(h))``; ``MLP(u) = W_d (SiLU(W_g u) * W_u u)``, ``C -> intermediate_size ->
C``, no bias; after the last block one LayerNorm, then ``logits = . E^T``.  The
kind of layer ``l`` (``mb_per_layer: 2``: every second layer of the self-decoder is
a scan; the self-decoder is layers ``0 .. L/2``)::

    l even, l <= L/2        ssm                a selective scan
    l odd,  l <  L/2        sliding_attention  differential attention, keys t - W + 1 .. t
    l = L/2 + 1             full_attention     differential attention, every key
    l even, l >  L/2 + 1    gmu                gates layer L/2's scan output
    l odd,  l >  L/2 + 1    cross_attention    queries alone; layer L/2 + 1's K and V

*Selective scan* (``d`` = 2 C, ``N`` = 16, ``R`` = ceil(C / 16), ``K`` = 4 taps)::

    [u | z]_t = W_in x_t                             C -> 2 d
    u_t <- SiLU(sum_{j < K} w[:, j] u_{t-(K-1)+j} + b)   depthwise, causal, u_s = 0 for s < 0
    [r | B | C]_t = W_x u_t                           d -> R + N + N
    dt_t = softplus(W_dt r_t + b_dt)                  R -> d
    S_t = exp(dt_t A) * S_{t-1} + (dt_t u_t) B_t^T    S (d, N) float32, A = -exp(A_log) (d, N)
    m_t = S_t C_t + D * u_t
    y_t = W_out (m_t * SiLU(z_t))                     d -> C
    a cache keeps S_t and the conv's inputs u_{t-1} .. u_{t-(K-1)} (before the conv)

*Differential attention* (``nh`` = 40 query heads, ``ng`` = 20 KV heads of ``hs`` = 64;
differential head ``j`` of ``nh / 2`` pairs queries ``(2j, 2j+1)``; its KV pair ``g = j //
(nh / ng)`` pairs keys ``(2g, 2g+1)`` and has ``V_g = [v_2g | v_2g+1]``, ``2 hs`` wide)::

    A_1 = softmax(q_2j k_2g^T / sqrt(hs)),  A_2 = softmax(q_2j+1 k_2g+1^T / sqrt(hs))    causal (and windowed)
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + l0,   l0 = 0.8 - 0.6 exp(-0.3 l)
    o_j = (1 - l0) RMSNorm_{2 hs}((A_1 - lambda A_2) V_g) g_sub
    y = W_o [o_0 .. o_{nh/2-1}] + b_o                 q, k, v = W x + b

*Gated memory unit*: ``y_t = W_2 (m_t * SiLU(W_1 x_t))``, ``C -> d -> C``, ``m`` layer
``L/2``'s at the same position.  *Cross attention*: the differential form with
the layer's own ``W_q``, ``lambda`` vectors, norm and ``W_o``; K and V are layer ``L/2 +
1``'s; causal, no window.

What the published config does not give is listed under ``assumed`` in the
configuration's file (Mamba's defaults, which heads pair, the biases, how the
weights are drawn).

Weight layout (what the program is handed; a linear layer is ``x @ W.T``):
``wte (V, C)`` (the head too), ``ln_f``, ``ln_f_b (C)``; a block: ``norm_1``,
``norm_1_b``, ``norm_2``, ``norm_2_b (C)``, ``mlp.fc_1/fc_2 (I, C)`` (gate, up),
``mlp.proj (C, I)``; an ssm block: ``ssm.in_proj (2 d, C)`` packed ``[u | z]``,
``ssm.conv_w (d, K)``, ``ssm.conv_b (d)``, ``ssm.x_proj (R + 2 N, d)`` packed ``[r | B |
C]``, ``ssm.dt_proj (d, R)``, ``ssm.dt_bias (d)``, ``ssm.A_log (d, N)``, ``ssm.D (d)`` (the
last three float32), ``ssm.out_proj (C, d)``; an attention block: ``attn.wq (nh hs,
C)``, ``attn.wk/wv (ng hs, C)``, ``attn.wo (C, nh hs)``, their biases ``bq, bk, bv, bo``,
``attn.lambda_q1/k1/q2/k2 (hs)`` float32, ``attn.subln (2 hs)``; a cross block: the
same without ``wk, wv, bk, bv``; a gmu block: ``gmu.in_proj (d, C)``, ``gmu.out_proj
(C, d)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.common import seed_key

OUTSIDE_BLOCKS = 0xFFFF   # the place folded into the key of the embedding
COLUMNS = 2560            # hidden columns of a SwiGLU whose weights are upcast at a time
HEAD_ROWS = 8192          # rows of the embedding whose float32 copy is made at a time, the most
QUERY_ROWS = 1024         # queries whose score matrices (a pair of KV heads') live at once
LAMBDA_STD = 0.1          # of the four lambda vectors
DT_RANGE = (1e-3, 0.1)    # the scan's time step at b_dt alone: log-uniform, as the layer that trains these models draws it


def sizes(hf: dict) -> dict:
    C, nh, L = hf["hidden_size"], hf["num_attention_heads"], hf["num_hidden_layers"]
    half = L // 2
    kinds = tuple("ssm" if l % 2 == 0 and l <= half else "sliding_attention" if l < half
                  else "full_attention" if l == half + 1 else "gmu" if l % 2 == 0 else "cross_attention"
                  for l in range(L))
    assert hf["mb_per_layer"] == 2, "every second layer of the self-decoder a scan"
    return {"C": C, "nh": nh, "ng": hf["num_key_value_heads"], "hs": hf.get("head_dim") or C // nh,
            "I": hf["intermediate_size"], "V": hf["vocab_size"], "L": L, "kinds": kinds, "W": hf["sliding_window"],
            "d": hf.get("mamba_expand", 2) * C, "N": hf.get("mamba_d_state", 16), "K": hf.get("mamba_d_conv", 4),
            "R": hf.get("mamba_dt_rank") or -(-C // 16), "eps": hf["layer_norm_eps"], "std": hf["initializer_range"],
            "source": half + 1, "memory": half,
            "L_ssm": kinds.count("ssm"), "L_swa": kinds.count("sliding_attention"),
            "L_walk_full": 1 + kinds.count("cross_attention")}


def program_config(hf: dict) -> dict:
    """The published keys under the names ``thunder_tpu.models.llama.Config``
    gives them."""
    s = sizes(hf)
    return dict(name=hf["model_name"], block_size=hf["max_position_embeddings"], vocab_size=s["V"],
                padded_vocab_size=s["V"], n_layer=s["L"], n_head=s["nh"], n_embd=s["C"], head_size=s["hs"],
                n_query_groups=s["ng"], norm_eps=s["eps"], norm_class="LayerNorm", rotary_percentage=0.0,
                tie_embeddings=True, intermediate_size=s["I"], layer_types=s["kinds"], layer_window=s["W"],
                diff_attention=True, ssm_inner=s["d"], ssm_state=s["N"], ssm_dt_rank=s["R"], ssm_conv_kernel=s["K"])


def layer_params(hf: dict, i: int) -> int:
    s = sizes(hf)
    C, d, N, R, K, hs = s["C"], s["d"], s["N"], s["R"], s["K"], s["hs"]
    q, kv = s["nh"] * hs, s["ng"] * hs
    diff = 4 * hs + 2 * hs
    mixer = {"ssm": 2 * d * C + d * K + d + (R + 2 * N) * d + d * R + d + d * N + d + C * d,
             "sliding_attention": C * (2 * q + 2 * kv) + q + 2 * kv + C + diff,
             "full_attention": C * (2 * q + 2 * kv) + q + 2 * kv + C + diff,
             "cross_attention": C * 2 * q + q + C + diff,
             "gmu": 2 * d * C}[s["kinds"][i]]
    return mixer + 3 * C * s["I"] + 4 * C


def param_count(hf: dict) -> int:
    """The tied embedding counted once."""
    s = sizes(hf)
    return sum(layer_params(hf, i) for i in range(s["L"])) + s["V"] * s["C"] + 2 * s["C"]


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """What the paged cache keeps a token of a request's whole length: K and V of the one full_attention layer."""
    s = sizes(hf)
    return 2 * s["ng"] * s["hs"] * itemsize


def ring_bytes_per_slot(hf: dict, block_size: int = 16, itemsize: int = 2) -> int:
    """What a request keeps of its sliding_attention layers, whatever its length: a ring of the window and a block."""
    s = sizes(hf)
    return s["L_swa"] * (-(-s["W"] // block_size) + 1) * block_size * 2 * s["ng"] * s["hs"] * itemsize


def state_bytes_per_slot(hf: dict, itemsize: int = 2) -> int:
    """The scans' float32 states and their conv tails."""
    s = sizes(hf)
    return s["L_ssm"] * (s["d"] * s["N"] * 4 + (s["K"] - 1) * s["d"] * itemsize)


def make_params(hf: dict, seed_words, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed (``common.seed_words``, an argument of the
    caller's ``jit``, not a constant of it), made where that ``jit`` puts them.
    Each leaf's key is folded from its place in the model, so layer ``i`` is the
    same whatever the depth.  Matrices and biases normal of std
    ``initializer_range``; norms 1 (their biases normal too); the lambda vectors
    normal of std 0.1 in float32; the scan's ``b_dt`` the inverse softplus of a
    time step drawn log-uniform in [0.001, 0.1], ``A_log = log(1 .. N)`` a
    channel, ``D = 1``, as the layer that trains these models starts them: a
    state then remembers tens to thousands of tokens, and a check sees its storage."""
    s = sizes(hf)
    C, d, N, R, K, hs, nh, ng = s["C"], s["d"], s["N"], s["R"], s["K"], s["hs"], s["nh"], s["ng"]
    root = seed_key(seed_words)
    key = lambda tag, layer: jax.random.fold_in(jax.random.fold_in(root, layer), tag)  # noqa: E731

    def dense(tag: int, layer: int, *shape):
        return (jax.random.normal(key(tag, layer), shape, jnp.float32) * s["std"]).astype(dtype)

    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    blocks = []
    for i, kind in enumerate(s["kinds"]):
        block = {"norm_1": ones(C), "norm_1_b": dense(20, i, C), "norm_2": ones(C), "norm_2_b": dense(21, i, C),
                 "mlp": {"fc_1": dense(5, i, s["I"], C), "fc_2": dense(6, i, s["I"], C), "proj": dense(7, i, C, s["I"])}}
        if kind == "ssm":
            lo, hi = math.log(DT_RANGE[0]), math.log(DT_RANGE[1])
            dt = jnp.exp(jax.random.uniform(key(13, i), (d,), jnp.float32, lo, hi))
            block["ssm"] = {"in_proj": dense(0, i, 2 * d, C), "conv_w": dense(1, i, d, K), "conv_b": dense(2, i, d),
                            "x_proj": dense(3, i, R + 2 * N, d), "dt_proj": dense(4, i, d, R),
                            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (d, N)),
                            "D": jnp.ones((d,), jnp.float32), "out_proj": dense(8, i, C, d)}
        elif kind == "gmu":
            block["gmu"] = {"in_proj": dense(0, i, d, C), "out_proj": dense(1, i, C, d)}
        else:
            lam = jax.random.normal(key(14, i), (4, hs), jnp.float32) * LAMBDA_STD
            attn = {"wq": dense(0, i, nh * hs, C), "bq": dense(9, i, nh * hs), "wo": dense(3, i, C, nh * hs),
                    "bo": dense(12, i, C), "lambda_q1": lam[0], "lambda_k1": lam[1], "lambda_q2": lam[2],
                    "lambda_k2": lam[3], "subln": ones(2 * hs)}
            if kind != "cross_attention":
                attn.update(wk=dense(1, i, ng * hs, C), wv=dense(2, i, ng * hs, C), bk=dense(10, i, ng * hs),
                            bv=dense(11, i, ng * hs))
            block["attn"] = attn
        blocks.append(block)
    return {"wte": dense(0, OUTSIDE_BLOCKS, s["V"], C), "blocks": blocks, "ln_f": ones(C),
            "ln_f_b": dense(1, OUTSIDE_BLOCKS, C)}


# --------------------------------------------------------------------------
# The plain reference: float32, highest matmul precision, no kernel, no cache
# --------------------------------------------------------------------------

def _f32(w):
    return w.astype(jnp.float32)


def _lin(x, w, b=None):
    y = x @ _f32(w).T
    return y if b is None else y + _f32(b)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(w) + _f32(b)


def conv_inputs(x, sp):
    """``u`` (before the conv) and ``z`` of every token, ``(T, d)`` each."""
    u, z = jnp.split(_lin(x, sp["in_proj"]), 2, axis=-1)
    return u, z


def scan(x, sp, s, live=None):
    """A selective scan over the whole sequence ``x (T, C)``, a token at a time:
    ``(m (T, d), z (T, d), the state after the last token (d, N))``.  ``live (T,
    1)`` bool: the rows past it are padding and must leave the state alone: their
    time step is zero."""
    T, K, N, R = x.shape[0], s["K"], s["N"], s["R"]
    u, z = conv_inputs(x, sp)
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    w = _f32(sp["conv_w"])
    u = jax.nn.silu(sum(padded[j:j + T] * w[:, j] for j in range(K)) + _f32(sp["conv_b"]))
    rbc = _lin(u, sp["x_proj"])
    r, Bm, Cm = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    dt = jax.nn.softplus(_lin(r, sp["dt_proj"]) + _f32(sp["dt_bias"]))
    if live is not None:
        dt = jnp.where(live, dt, 0.0)
    A = -jnp.exp(_f32(sp["A_log"]))                                       # (d, N)

    def step(S, a):
        u_t, dt_t, b_t, c_t = a
        S = jnp.exp(dt_t[:, None] * A) * S + (dt_t * u_t)[:, None] * b_t[None, :]
        return S, S @ c_t

    S, y = jax.lax.scan(step, jnp.zeros((s["d"], N), jnp.float32), (u, dt, Bm, Cm))
    return y + _f32(sp["D"]) * u, z, S


def _ssm(x, sp, s):
    m, z, _ = scan(x, sp, s)
    return _lin(m * jax.nn.silu(z), sp["out_proj"]), m


def keys_values(x, ap, s):
    """What a cache holds of every token: keys and values ``(T, ng, hs)`` each."""
    T = x.shape[0]
    return (_lin(x, ap["wk"], ap["bk"]).reshape(T, s["ng"], s["hs"]),
            _lin(x, ap["wv"], ap["bv"]).reshape(T, s["ng"], s["hs"]))


def _diff_attention(x, ap, s, layer, window, kv):
    """``x (T, C)`` gives the queries, ``kv = (k, v)`` ``(T, ng, hs)`` each the keys
    and values.  A pair of KV heads at a time, ``QUERY_ROWS`` queries at a time:
    the score matrices of one such block live at once."""
    T, nh, ng, hs = x.shape[0], s["nh"], s["ng"], s["hs"]
    J = nh // ng                                                          # differential heads a KV pair
    rows = QUERY_ROWS if T % QUERY_ROWS == 0 else T
    q = _lin(x, ap["wq"], ap["bq"]).reshape(T // rows, rows, ng // 2, J, 2, hs)   # (block, t, pair g, head j, first / second, hs)
    k, v = (a.reshape(T, ng // 2, 2, hs).swapaxes(0, 1) for a in kv)              # (pair g, T, 2, hs)
    l0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(_f32(ap["lambda_q1"]) * _f32(ap["lambda_k1"])))
           - jnp.exp(jnp.sum(_f32(ap["lambda_q2"]) * _f32(ap["lambda_k2"]))) + l0)

    def block(a):
        qb, first = a                                                     # (rows, ng / 2, J, 2, hs), the block's first position
        t = first + jnp.arange(rows)
        keep = jnp.arange(T)[None, :] <= t[:, None]
        if window is not None:
            keep = jnp.logical_and(keep, jnp.arange(T)[None, :] > t[:, None] - window)

        def pair(b):
            qg, kg, vg = b                                                # (rows, J, 2, hs), (T, 2, hs), (T, 2, hs)
            sc = jnp.einsum("tjph,sph->pjts", qg, kg) / math.sqrt(hs)
            w = jax.nn.softmax(jnp.where(keep[None, None], sc, -jnp.inf), axis=-1)   # (2, J, rows, T)
            o = jnp.einsum("jts,sd->tjd", w[0] - lam * w[1], vg.reshape(T, 2 * hs))
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + s["eps"]) * _f32(ap["subln"])
            return (1.0 - l0) * o                                         # (rows, J, 2 hs)

        return jax.lax.map(pair, (qb.swapaxes(0, 1), k, v)).swapaxes(0, 1)            # (rows, ng / 2, J, 2 hs)

    o = jax.lax.map(block, (q, jnp.arange(T // rows) * rows))
    return _lin(o.reshape(T, nh * hs), ap["wo"], ap["bo"])


def _swiglu(h, mp):
    """``W_d (silu(W_g h) * W_u h)``, ``COLUMNS`` hidden columns at a time: the
    float32 copy of that many columns' weights, and no more."""
    I = mp["fc_1"].shape[0]
    n = COLUMNS if I % COLUMNS == 0 else I

    def columns(y, c):
        w1, w2 = (jax.lax.dynamic_slice_in_dim(mp[k], c * n, n, axis=0) for k in ("fc_1", "fc_2"))
        w3 = jax.lax.dynamic_slice_in_dim(mp["proj"], c * n, n, axis=1)
        return y + (jax.nn.silu(h @ _f32(w1).T) * (h @ _f32(w2).T)) @ _f32(w3).T, None

    return jax.lax.scan(columns, jnp.zeros_like(h), jnp.arange(I // n))[0]


def _block(x, bp, s, layer, memory, shared):
    """One block on ``x (T, C)`` float32.  ``memory``: the scan output a gmu
    gates; ``shared``: the K and V a cross layer reads.  Returns the block's
    output and what it hands on (its ``m``, or its K and V, or None)."""
    kind = s["kinds"][layer]
    n1 = _layer_norm(x, bp["norm_1"], bp["norm_1_b"], s["eps"])
    hands = None
    if kind == "ssm":
        mix, hands = _ssm(n1, bp["ssm"], s)
    elif kind == "gmu":
        mix = _lin(memory * jax.nn.silu(_lin(n1, bp["gmu"]["in_proj"])), bp["gmu"]["out_proj"])
    elif kind == "cross_attention":
        mix = _diff_attention(n1, bp["attn"], s, layer, None, shared)
    else:
        hands = keys_values(n1, bp["attn"], s)
        mix = _diff_attention(n1, bp["attn"], s, layer, s["W"] if kind == "sliding_attention" else None, hands)
    h = x + mix
    return h + _swiglu(_layer_norm(h, bp["norm_2"], bp["norm_2_b"], s["eps"]), bp["mlp"]), hands


def _hf_items(hf: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in hf.items() if not isinstance(v, (dict, list))))


@functools.partial(jax.jit, static_argnames=("hf_items", "layer"))
def _ref_block(x, bp, memory, shared, *, hf_items, layer):
    with jax.default_matmul_precision("highest"):
        return _block(x, bp, sizes(dict(hf_items)), layer, memory, shared)


@functools.partial(jax.jit, static_argnames=("hf_items", "layer"))
def _ref_held(x, bp, n_real, *, hf_items, layer):
    """What a cache holds of this layer once the first ``n_real`` tokens of ``x``
    went in.  ssm: the state ``(d, N)`` after token ``n_real - 1`` and the conv's
    tail ``(K - 1, d)`` (``u`` before the conv of tokens ``n_real - (K - 1) ..
    n_real - 1``, zeros before the first).  An attention layer: every token's
    key and value ``(ng, T, hs)``."""
    with jax.default_matmul_precision("highest"):
        s = sizes(dict(hf_items))
        n1 = _layer_norm(x, bp["norm_1"], bp["norm_1_b"], s["eps"])
        if s["kinds"][layer] == "ssm":
            _, _, S = scan(n1, bp["ssm"], s, live=(jnp.arange(x.shape[0]) < n_real)[:, None])
            u, _ = conv_inputs(n1, bp["ssm"])
            return S, jax.lax.dynamic_slice_in_dim(jnp.pad(u, ((s["K"] - 1, 0), (0, 0))), n_real, s["K"] - 1, axis=0)
        return tuple(a.swapaxes(0, 1) for a in keys_values(n1, bp["attn"], s))


@functools.partial(jax.jit, static_argnames=("eps",))
def _ref_logits(x, ln_f, ln_f_b, head, *, eps):
    """The last norm and the head, a whole number of the embedding's rows at a
    time, ``HEAD_ROWS`` the most, each cut out of the embedding where it lies (its
    float32 copy whole is 2 GB, and a reshape to blocks of rows is a copy of its
    own on the chip: 1.15 GB of temporaries, chip call 4, PR 41)."""
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(x, ln_f, ln_f_b, eps)
        V = head.shape[0]
        rows = max(r for r in range(1, min(V, HEAD_ROWS) + 1) if V % r == 0)
        out = jax.lax.map(lambda i: x @ _f32(jax.lax.dynamic_slice_in_dim(head, i * rows, rows, axis=0)).T,
                          jnp.arange(V // rows))                                           # (V / rows, T, rows)
        return out.swapaxes(0, 1).reshape(x.shape[0], V)


def _walk(hf: dict, params: dict, tokens, visit=None):
    """The blocks in order on one sequence: yields ``(layer, kind, x before the block, block)``."""
    s, items = sizes(hf), _hf_items(hf)
    x = params["wte"][tokens].astype(jnp.float32)
    memory = shared = None
    for layer, bp in enumerate(params["blocks"]):
        if visit is not None:
            visit(layer, s["kinds"][layer], x, bp)
        x, hands = _ref_block(x, bp, memory if s["kinds"][layer] == "gmu" else None,
                              shared if s["kinds"][layer] == "cross_attention" else None, hf_items=items, layer=layer)
        # a block at a time on the device too: a call's results are allocated when it is enqueued, and
        # 32 blocks enqueued ahead held 2.5 GB beside the engine's arenas (chip calls 6-8, PR 41)
        jax.block_until_ready(x)
        if layer == s["memory"]:
            memory = hands
        elif layer == s["source"]:
            shared = hands
        del hands
    return x


def ref_hidden(hf: dict, params: dict, tokens):
    """Final hidden states ``(T, C)`` before the last norm, for one sequence of
    token ids ``(T,)``.  One block's weights are upcast at a time."""
    return _walk(hf, params, tokens)


def ref_caches(hf: dict, params: dict, tokens, n_real: int):
    """What a server's caches hold of one sequence after its first ``n_real``
    tokens (the rest of ``tokens`` is padding), by the kind of each layer, in the
    model's order, all float32: ``("ssm", (state (d, N), tail (K - 1, d)))``;
    ``("sliding_attention", (k, v))``, each ``(ng, n, hs)``, the last ``n = min(n_real,
    W)`` tokens' in order; ``("full_attention", (k, v))``, each ``(ng, n_real, hs)``;
    ``("gmu", None)`` and ``("cross_attention", None)``, which keep nothing.  A list
    of the layers that keep something, made a layer at a time."""
    s, items, out = sizes(hf), _hf_items(hf), []

    def visit(layer, kind, x, bp):
        if kind in ("gmu", "cross_attention"):
            out.append((kind, None))
            return
        got = jax.device_get(_ref_held(x, bp, n_real, hf_items=items, layer=layer))
        if kind == "ssm":
            out.append((kind, got))
        else:
            lo = max(0, n_real - s["W"]) if kind == "sliding_attention" else 0
            out.append((kind, tuple(a[:, lo:n_real] for a in got)))

    _walk(hf, params, tokens, visit)
    return out


def ref_logits(hf: dict, params: dict, tokens, positions):
    """Logits ``(len(positions), V)`` of the full forward pass at the given
    positions of one sequence; the head is the embedding."""
    x = ref_hidden(hf, params, tokens)
    return _ref_logits(x[positions], params["ln_f"], params["ln_f_b"], params["wte"], eps=hf["layer_norm_eps"])
