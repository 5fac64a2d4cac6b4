"""A sparse latent-attention decoder whose residual stream is several copies wide
under manifold-constrained hyper-connections, as Xing4.0-29B-A4B's public
``config.json`` describes it (``model_type: xing4_0``): DeepSeek-V3's attention and
expert layer at other numbers, and a changed block.  This file is the benchmark's
own copy of the architecture: the weights it makes from the seed, the plain
float32 reference with the stream carried through (no cache, no absorbed weights,
no kernel, the experts by a mask), and the sizes the per-layer readers count
from.  It reads the published keys of the configuration file and imports nothing
of the program under test.

**What is ``latent_moe_decoder.py``'s**, at this configuration's numbers: latent
attention (``C`` 3584, 32 heads, ``q_lora_rank`` 768, ``kv_lora_rank`` 512, 128
unrotated + 64 rotated query dims, values of 128), YaRN (factor 64 over 4096: the
scores carry ``(0.1 ln 64 + 1)^2 = 2.005``), the dense SwiGLU (9216), the ungated
shared expert and the expert SwiGLU (1024), the weight layout.  Their equations are
in that file's docstring and their code is imported from it.

**The router** (``topk_method: noaux_tc``, ``n_group = topk_group = 1``: no groups)::

    s = sigmoid(W_g h)  in float32 over all 64
    the top num_experts_per_tok (4) of s + e_score_correction_bias
    w_i = s_i / (sum of the chosen s + 1e-6) * routed_scaling_factor (2)        norm_topk_prob
    y = sum_{i chosen} w_i E_i(h) + S(h)

The bias moves the choice and never a weight.

**The block** (mHC, arXiv:2512.24880, on hyper-connections, arXiv:2409.19606).
``n`` = ``hc_mult`` 4.  The stream is ``X_l (n, C)`` a token.  A sublayer ``F``
(attention with its ``rms_C``, or the feed-forward with its ``rms_C``) has its own
``phi_pre, phi_post (nC, n)``, ``phi_res (nC, n^2)``, a weight ``w (nC)`` of the
flattened norm, scalars ``a_pre, a_post, a_res``, biases ``b_pre, b_post (n)``,
``b_res (n, n)``::

    x' = rms_nC(vec(X_l)) * w                               one norm over the flattened 4C, eps hc_eps
    H~pre  = a_pre  * (x' phi_pre)  + b_pre                 (n)
    H~post = a_post * (x' phi_post) + b_post                (n)
    H~res  = a_res  * mat(x' phi_res) + b_res               (n, n), row-major: [i, j] at i n + j
    H_pre = sigmoid(H~pre)        H_post = 2 sigmoid(H~post)
    M_0 = exp(clip(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    hc_sinkhorn_iters (20) times:  M <- M / (its columns' sums + hc_eps),  then  M <- M / (its rows' sums + hc_eps)   -> H_res
    u = H_pre X_l                   (C)      what the sublayer reads
    X_{l+1} = H_res X_l + H_post^T F(u)      (n, C): stream i takes row i of H_res over the streams, and H_post[i] F

all of it in float32.  The embedding is copied to the ``n`` streams; the ``n``
streams are summed before the last ``rms_C`` and the untied head.  *Assumed* (the
configuration file's ``assumed`` says each with its paper): the copy and the sum
(arXiv:2409.19606, section 3); columns before rows inside an iteration, so that
the rows of ``H_res`` sum to one exactly and its columns to within the
iteration's convergence (arXiv:2512.24880 writes ``T_r(T_c(M))``), ``hc_eps``
added to each sum and to the flattened norm's mean square; the flattened norm has
a weight (ones here); ``vec`` runs a stream after a stream.

Weight layout beside ``latent_moe_decoder.py``'s: a block's ``hc_1`` (the
attention's) and ``hc_2`` (the feed-forward's), each ``phi (n (n + 2), n C)`` with
rows ``[pre (n) | post (n) | res (n^2)]`` (a linear layer is ``x @ W.T``), ``norm
(n C)``, ``alpha (3,)`` float32 ``[a_pre, a_post, a_res]``, ``bias (n (n + 2),)``
float32 in ``phi``'s row order; an expert layer's ``mlp.expert_bias (E,)`` float32.

The draws: matrices and ``phi`` normal of std ``initializer_range``; norms 1; the
router's bias ``N(0, 0.01^2)`` in float32; ``a_* = hc_alpha_init`` (0.4: with
``x'`` of unit mean square and ``phi`` of std 0.02 over 14,336 inputs the dynamic
part of every ``H~`` has std 0.4 x 0.02 x sqrt(14336) = 0.96, so a token's maps are
its own; the paper starts ``a`` at 0.01, which no check could see); ``b_pre =
b_post = 0``; ``b_res = hc_res_bias_diag`` (2) on the diagonal, 0 off it: ``H_res``
keeps 0.64 of a stream (a token's own share, std 0.12) and passes 0.12 to each
other one on average, neither the identity nor uniform; its columns sum to one
within 1e-6 for the median token and 0.02 for the worst of 200,000 draws.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.common import seed_key
from chipbench.models import latent_moe_decoder as base

BIAS_STD = 0.01           # the router's bias: it still moves the choice, and every expert is hit
HEAD_ROWS = 16384         # rows of the head whose float32 copy is made at a time

attn_scale, attn_params, expert_params, latent_bytes_per_token = (
    base.attn_scale, base.attn_params, base.expert_params, base.latent_bytes_per_token)


def sizes(hf: dict) -> dict:
    """``latent_moe_decoder.sizes`` (the keys the latent readers count from) and the hyper-connection's."""
    return {**base.sizes(hf), "n": hf["hc_mult"], "iters": hf["hc_sinkhorn_iters"], "hc_eps": hf["hc_eps"],
            "clamp": (float(hf["mhc_h_res_clamp_min"]), float(hf["mhc_h_res_clamp_max"])),
            "hc_alpha": hf["hc_alpha_init"], "hc_res_diag": hf["hc_res_bias_diag"]}


def program_config(hf: dict) -> dict:
    """The published keys under the names ``thunder_tpu.models.llama.Config`` gives them."""
    s = sizes(hf)
    return {**base.program_config(hf), "moe_router": "sigmoid_bias", "hc_mult": s["n"],
            "hc_sinkhorn_iters": s["iters"], "hc_eps": s["hc_eps"], "hc_res_clamp": s["clamp"]}


def hc_params(hf: dict) -> int:
    """A layer's two hyper-connections: ``phi``, the flattened norm's weight, three scalars and the biases, each."""
    s = sizes(hf)
    m = s["n"] * (s["n"] + 2)
    return 2 * (s["n"] * s["C"] * m + s["n"] * s["C"] + 3 + m)


def layer_params(hf: dict, i: int) -> int:
    s = sizes(hf)
    return base.layer_params(hf, i) + hc_params(hf) + (0 if i < s["dense"] else s["E"])    # the router's bias


def module_params(hf: dict) -> int:
    """One multi-token-prediction module as DeepSeek-V3 builds it: an expert layer, two norms
    and a projection ``(C, 2 C)`` of the embedding beside the hidden state (embedding and head shared)."""
    s = sizes(hf)
    return layer_params(hf, s["dense"]) + 2 * s["C"] + 2 * s["C"] * s["C"]


def param_count(hf: dict) -> int:
    """What the configuration holds: its layers, the embedding, the head and the last norm."""
    s = sizes(hf)
    return sum(layer_params(hf, i) for i in range(s["L"])) + 2 * s["V"] * s["C"] + s["C"]


def active_params(hf: dict) -> int:
    """Parameters a token is multiplied by: everything but the experts it is not
    routed to and the embedding (a lookup)."""
    s = sizes(hf)
    return param_count(hf) - (s["L"] - s["dense"]) * (s["E"] - s["k"]) * expert_params(hf) - s["V"] * s["C"]


def make_params(hf: dict, seed_words, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed (``common.seed_words``, an argument of the
    caller's ``jit``), the leaves and keys ``latent_moe_decoder.make_params`` gives
    (a leaf's key is folded from its place in the model, so layer ``i`` is the
    same whatever the depth, and an expert's from its number) and, a block, the
    two hyper-connections and the router's bias, keyed the same way.  A layer's
    64 experts are one batched draw a matrix: drawn one by one (960 draws and
    15 concatenations of 64 pieces) the program took the chip's compiler 543 s
    (my chip run, PR 53, call 1)."""
    s = sizes(hf)
    n, C = s["n"], s["C"]
    m = n * (n + 2)
    root = seed_key(seed_words)
    key = lambda tag, layer: jax.random.fold_in(jax.random.fold_in(root, layer), tag)  # noqa: E731

    def dense(tag: int, layer: int, fan_out: int, fan_in: int):
        return (jax.random.normal(key(tag, layer), (fan_out, fan_in), jnp.float32) * s["std"]).astype(dtype)

    def experts(tag: int, layer: int, fan_in: int, fan_out: int):
        """The held experts' matrices, ``x @ W`` layout, stacked on rows: ``(held fan_in, fan_out)``."""
        one = lambda e: (jax.random.normal(jax.random.fold_in(key(tag, layer), e), (fan_in, fan_out),  # noqa: E731
                                           jnp.float32) * s["std"]).astype(dtype)
        return jax.lax.map(one, s["first"] + jnp.arange(s["held"])).reshape(s["held"] * fan_in, fan_out)

    ones = lambda k: jnp.ones((k,), dtype)  # noqa: E731
    hc_bias = jnp.concatenate([jnp.zeros((2 * n,)), s["hc_res_diag"] * jnp.eye(n).reshape(-1)]).astype(jnp.float32)
    hc = lambda tag, i: {"phi": dense(tag, i, m, n * C), "norm": ones(n * C),  # noqa: E731
                         "alpha": jnp.full((3,), s["hc_alpha"], jnp.float32), "bias": hc_bias}
    blocks = []
    for i in range(s["L"]):
        attn = {"wq_a": dense(0, i, s["rq"], C), "q_norm": ones(s["rq"]),
                "wq_b": dense(1, i, s["nh"] * (s["dn"] + s["dr"]), s["rq"]),
                "wkv_a": dense(2, i, s["dc"] + s["dr"], C), "kv_norm": ones(s["dc"]),
                "wkv_b": dense(3, i, s["nh"] * (s["dn"] + s["dv"]), s["dc"]),
                "wo": dense(4, i, C, s["nh"] * s["dv"])}
        if i < s["dense"]:
            mlp = {"fc_1": dense(5, i, s["I"], C), "fc_2": dense(6, i, s["I"], C), "proj": dense(7, i, C, s["I"])}
        else:
            mlp = {"gate": dense(8, i, s["E"], C),
                   "fc_1": experts(9, i, C, s["Im"]), "fc_2": experts(10, i, C, s["Im"]),
                   "proj": experts(11, i, s["Im"], C),
                   "shared": {"fc_1": dense(12, i, s["Is"], C), "fc_2": dense(13, i, s["Is"], C),
                              "proj": dense(14, i, C, s["Is"])},
                   "expert_bias": jax.random.normal(key(17, i), (s["E"],), jnp.float32) * BIAS_STD}
        blocks.append({"norm_1": ones(C), "attn": attn, "norm_2": ones(C), "mlp": mlp,
                       "hc_1": hc(15, i), "hc_2": hc(16, i)})
    return {"wte": dense(0, base.OUTSIDE_BLOCKS, s["V"], C), "blocks": blocks,
            "ln_f": ones(C), "lm_head": dense(1, base.OUTSIDE_BLOCKS, s["V"], C)}


# --------------------------------------------------------------------------
# The plain reference: float32, highest matmul precision, no kernel, no cache
# --------------------------------------------------------------------------

def hc_maps(X, hp, s):
    """A token's three maps from its stream ``X (T, n, C)`` float32: ``H_pre (T,
    n)``, ``H_post (T, n)``, ``H_res (T, n, n)``."""
    T, n, C = X.shape
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    v = X.reshape(T, n * C)
    xn = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + s["hc_eps"]) * f32(hp["norm"])
    raw = xn @ f32(hp["phi"]).T
    a, b = f32(hp["alpha"]), f32(hp["bias"])
    h_pre = jax.nn.sigmoid(a[0] * raw[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * raw[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(a[2] * raw[:, 2 * n:] + b[2 * n:], *s["clamp"])).reshape(T, n, n)
    for _ in range(s["iters"]):
        M = M / (jnp.sum(M, axis=1, keepdims=True) + s["hc_eps"])       # a column's entries, over the rows i
        M = M / (jnp.sum(M, axis=2, keepdims=True) + s["hc_eps"])       # a row's entries, over the columns j
    return h_pre, h_post, M


def hc_read(X, h_pre):
    """``u = H_pre X (T, C)``."""
    return jnp.einsum("tj,tjc->tc", h_pre, X)


def hc_write(X, f, h_post, h_res):
    """``H_res X + H_post^T f (T, n, C)``."""
    return jnp.einsum("tij,tjc->tic", h_res, X) + h_post[:, :, None] * f[:, None, :]


def route(h, gate, bias, s):
    """The biased choice: ``(weights (T, k), experts (T, k))`` over all E."""
    sc = jax.nn.sigmoid(base._lin(h, gate))
    _, idx = jax.lax.top_k(sc + bias.astype(jnp.float32), s["k"])
    w = jnp.take_along_axis(sc, idx, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * s["route_scale"], idx


def _expert_share(h, mp, s):
    """Every token through every expert, one expert at a time, weighted by what
    the router gave it (zero if not chosen); the shared expert once."""
    C, Im, held = s["C"], s["Im"], s["held"]
    w, idx = route(h, mp["gate"], mp["expert_bias"], s)
    fc_1, fc_2, proj = (mp["fc_1"].reshape(held, C, Im), mp["fc_2"].reshape(held, C, Im),
                        mp["proj"].reshape(held, Im, C))

    def one(y, a):
        e, w1, w2, w3 = a
        we = jnp.sum(jnp.where(idx == s["first"] + e, w, 0.0), axis=-1)                  # (T,)
        f32 = lambda m: m.astype(jnp.float32)  # noqa: E731
        rows = lambda hb, _: (jax.nn.silu(hb @ f32(w1)) * (hb @ f32(w2))) @ f32(w3)  # noqa: E731
        return y + we[:, None] * base._by_rows(rows, h), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(held), fc_1, fc_2, proj))
    return y + base._swiglu(h, mp["shared"])


def _attn_input(X, bp, s):
    """What the attention reads, and the maps it writes back by."""
    h_pre, h_post, h_res = hc_maps(X, bp["hc_1"], s)
    return base._rms(hc_read(X, h_pre), bp["norm_1"], s["eps"]), h_post, h_res


def _block(X, bp, cos, sin, s, dense):
    """One block on the stream ``X (T, n, C)`` float32."""
    n1, h_post, h_res = _attn_input(X, bp, s)
    X = hc_write(X, base._attention(n1, bp["attn"], cos, sin, s), h_post, h_res)
    h_pre, h_post, h_res = hc_maps(X, bp["hc_2"], s)
    n2 = base._rms(hc_read(X, h_pre), bp["norm_2"], s["eps"])
    return hc_write(X, base._swiglu(n2, bp["mlp"]) if dense else _expert_share(n2, bp["mlp"], s), h_post, h_res)


_hf_items = base._hf_items


@functools.partial(jax.jit, static_argnames=("hf_items", "dense"))
def _ref_block(X, bp, *, hf_items, dense):
    with jax.default_matmul_precision("highest"):
        s = sizes(dict(hf_items))
        cos, sin = base.rope_tables(s, X.shape[0])
        return _block(X, bp, cos, sin, s, dense)


@functools.partial(jax.jit, static_argnames=("hf_items",))
def _ref_latent(X, bp, *, hf_items):
    with jax.default_matmul_precision("highest"):
        s = sizes(dict(hf_items))
        cos, sin = base.rope_tables(s, X.shape[0])
        return base.latent(_attn_input(X, bp, s)[0], bp["attn"], cos, sin, s)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ref_logits(X, ln_f, head, *, eps):
    """The streams summed, the last norm, and the head ``HEAD_ROWS`` rows at a time."""
    with jax.default_matmul_precision("highest"):
        x = base._rms(jnp.sum(X, axis=1), ln_f, eps)
        V = head.shape[0]
        n = HEAD_ROWS if V % HEAD_ROWS == 0 else V
        out = jax.lax.map(lambda w: x @ w.astype(jnp.float32).T, head.reshape(V // n, n, head.shape[1]))
        return out.transpose(1, 0, 2).reshape(x.shape[0], V)


def _streams(hf: dict, params: dict, tokens):
    x = params["wte"][tokens].astype(jnp.float32)
    return jnp.broadcast_to(x[:, None], (x.shape[0], hf["hc_mult"], x.shape[1]))


def ref_hidden(hf: dict, params: dict, tokens):
    """The final stream ``(T, n, C)`` before the sum and the last norm, for one
    sequence of token ids ``(T,)``.  One block's weights are upcast at a time, an
    expert's inside it."""
    items, dense = _hf_items(hf), sizes(hf)["dense"]
    X = _streams(hf, params, tokens)
    for i, bp in enumerate(params["blocks"]):
        X = _ref_block(X, bp, hf_items=items, dense=i < dense)
    return X


def ref_latents(hf: dict, params: dict, tokens, n_real: int):
    """What a server's cache holds of one sequence after its first ``n_real``
    tokens (the rest of ``tokens`` is padding), a layer at a time: ``(n_real,
    dc + dr)`` float32, the normed latent and the rotated key.  A generator."""
    items, dense = _hf_items(hf), sizes(hf)["dense"]
    X = _streams(hf, params, tokens)
    for i, bp in enumerate(params["blocks"]):
        yield _ref_latent(X, bp, hf_items=items)[:n_real]
        X = _ref_block(X, bp, hf_items=items, dense=i < dense)


def ref_logits(hf: dict, params: dict, tokens, positions):
    """Logits ``(len(positions), V)`` of the full forward pass at the given
    positions of one sequence."""
    X = ref_hidden(hf, params, tokens)
    return _ref_logits(X[positions], params["ln_f"], params["lm_head"], eps=hf["rms_norm_eps"])
