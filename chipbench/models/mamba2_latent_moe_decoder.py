"""A hybrid of Mamba-2 layers, latent sparse experts and a few attention layers
as NVIDIA-Nemotron-3-Super-120B-A12B's public ``config.json`` describes it
(``model_type: nemotron_h``): every layer is *one* sublayer, a Mamba-2 mixer
(``M``), an attention mixer (``*``) or an expert feed-forward (``E``), in the
order ``hybrid_override_pattern`` gives; the head is not tied to the embedding.
This file is the benchmark's own copy of the architecture: the weights it makes
from the seed, the plain float32 reference (no cache, no kernel, no chunking:
the scan a token at a time over the whole sequence, attention by the full
causal score matrix, the experts by a mask one at a time) and the sizes the
per-layer readers count from.  It reads the published keys of the
configuration file and imports nothing of the program under test.

The equations (``C`` = hidden size 4096, eps ``norm_eps`` 1e-5, no bias but the
conv's).  ``RMSNorm_n(x) = x / sqrt(mean_n(x^2) + eps) * w``.  Layer ``l`` of the
pattern: ``x <- x + f_l(RMSNorm(x))``; after the last, ``logits = RMSNorm(x)
W_head``.

``M``: *Mamba-2* (``H`` = 128 heads of ``P`` = 64 channels, ``d`` = H P = 8192; ``G`` = 8
groups of heads; ``N`` = 128 states; ``K`` = 4 taps)::

    [z | xBC | dt]_t = W_in u_t                       C -> d + (d + 2 G N) + H = 18,560
    xBC_t <- SiLU(sum_{j < K} w[:, j] xBC_{t-(K-1)+j} + b)   depthwise, causal, over 10,240 channels
    [x | B | C]_t = xBC_t                             x (H, P); B, C (G, N); head h reads group h // (H / G)
    dt_t <- softplus(dt_t + dt_bias)                  a head;  A = -exp(A_log), a head
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] B_t[g]^T       (P, N), float32
    y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
    y_t <- RMSNorm_{d / G}(y_t * SiLU(z_t)) w         the gate before the norm, the norm a group's 1024 channels
    out_t = W_out y_t                                 d -> C
    a cache keeps S_t and the conv's inputs xBC_{t-1} .. xBC_{t-(K-1)} (before the conv)

``chunk_size`` 128 is the block of the published scan kernel and no part of the
maths.

``*``: *attention*: ``nh`` = 32 query heads over ``ng`` = 2 KV heads of ``hs`` = 128,
``y = W_o softmax_causal(q k^T hs^-1/2) v``, no bias, **no rotary embedding**
(the family's Nemotron-H layers have none; the config's ``rope_theta`` is
vestigial).

``E``: *latent experts* (``E`` = 512 routed experts of width ``Im`` = 2688 in a
latent of ``Cl`` = 1024, ``k`` = 22 a token, one shared expert of 5376 at the full
width; ``n_group = topk_group = 1``: no groups)::

    s = sigmoid(W_g u)                                over all E, in float32
    chosen = top-k of (s + b)                         b (E,) float32 (``e_score_correction_bias``)
    w_i = routed_scaling_factor * s_i / (sum_chosen s + 1e-6)           (``norm_topk_prob``)
    v = W_down u                                      C -> Cl
    r = sum_{i in chosen} w_i W2_i relu(W1_i v)^2     W1_i: Cl -> Im, W2_i: Im -> Cl; no gate
    out = W_up r + W2_s relu(W1_s u)^2                Cl -> C; the shared expert ungated, at width 5376

Departures from the published model, each noted in the configuration's
``assumed``: the multi-token-prediction module (``mtp_hybrid_override_pattern``
``*E``, ``num_nextn_predict_layers`` 1) is left out: it adds nothing to a served
logit; the router's product runs in float32 here and in the program; ``b`` is
drawn from the seed, normal of std ``BIAS_STD`` 0.01 (the published one is trained to even the
experts' load, hf starts it at zero where it would test nothing), as are ``dt_bias`` (the inverse softplus of a step
log-uniform in ``time_step_min``-``time_step_max``), ``A_log = log U(1, 16)`` a head
and ``D = 1``: as the layer that trains these models draws them, so that a state
remembers tens to thousands of tokens and a check sees its storage; the
renormalisation adds 1e-6 where hf adds 1e-20; no rotary embedding.

A chip's share (``n_routed_experts`` held of ``published_n_routed_experts``,
starting at ``expert_first``; ``vocab_size`` rows of ``published_vocab_size``): the
router scores all 512 and chooses 22; the held experts' part of ``r`` is
computed, what the others would add is left out, here and in the program alike;
the latent projections and the shared expert are whole.

Weight layout (what the program is handed; a linear layer is ``x @ W.T`` unless
said otherwise): ``wte (V, C)``, ``lm_head (V, C)``, ``ln_f (C)``; every block:
``norm_1 (C)``; an ``M`` block: ``mamba2.in_proj (d + d + 2 G N + H, C)`` packed ``[z |
x | B | C | dt]``, ``mamba2.conv_w (d + 2 G N, K)``, ``mamba2.conv_b (d + 2 G N)``,
``mamba2.dt_bias``, ``mamba2.A_log``, ``mamba2.D (H)`` float32, ``mamba2.norm (d)``,
``mamba2.out_proj (C, d)``; a ``*`` block: ``attn.wq (nh hs, C)``, ``attn.wk/wv (ng hs,
C)``, ``attn.wo (C, nh hs)``; an ``E`` block: ``mlp.gate (E, C)``, ``mlp.expert_bias (E)``
float32, ``mlp.latent_down (Cl, C)``, ``mlp.latent_up (C, Cl)``, ``mlp.fc_1 (held Cl,
Im)`` and ``mlp.proj (held Im, Cl)`` (the held experts stacked, ``x @ W`` layout:
expert ``e`` is rows ``[e Cl, (e + 1) Cl)``), ``mlp.shared.fc_1 (Is, C)``,
``mlp.shared.proj (C, Is)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.common import seed_key

OUTSIDE_BLOCKS = 0xFFFF   # the place folded into the key of the embedding and the head
ROWS = 512                # rows of an expert at a time
HEAD_ROWS = 8192          # rows of the head whose float32 copy is made at a time, the most
QUERY_ROWS = 1024         # queries whose score matrices (one KV head's) live at once
# of the router's bias.  The top 22 of 512 sit where a sigmoid is near 0.9 and lie within a few hundredths of one
# another: at std 0.1 (the spread that serves a router choosing 4 of 32) the bias bars a sixth of the experts for good
# and the held ones saw 0.51-0.58 of themselves hit a decode step, by the seed (PERF.md section 6, PR 45); the
# published bias is trained to even the load.  At 0.01 it still moves the choice and every held expert is hit
BIAS_STD = 0.01
A_RANGE = (1.0, 16.0)     # -A a head: uniform, as the layer that trains these models draws it
KINDS = {"M": "mamba2", "E": "mlp", "*": "full_attention"}


def sizes(hf: dict) -> dict:
    L = hf["num_hidden_layers"]
    pattern = hf["hybrid_override_pattern"][:L]      # the published pattern, as deep as this cut
    assert len(pattern) == L and set(pattern) <= set(KINDS), pattern
    kinds = tuple(KINDS[c] for c in pattern)
    H, P, G, N = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["n_groups"], hf["ssm_state_size"]
    assert hf["n_group"] == 1 and hf["topk_group"] == 1, "the router has no groups"
    return {"C": hf["hidden_size"], "L": L, "kinds": kinds, "pattern": pattern, "V": hf["vocab_size"],
            "H": H, "P": P, "G": G, "N": N, "K": hf["conv_kernel"], "d": H * P, "W": H * P + 2 * G * N,
            "nh": hf["num_attention_heads"], "ng": hf["num_key_value_heads"], "hs": hf["head_dim"],
            "E": hf.get("published_n_routed_experts", hf["n_routed_experts"]), "held": hf["n_routed_experts"],
            "first": hf.get("expert_first", 0), "k": hf["num_experts_per_tok"], "Im": hf["moe_intermediate_size"],
            "Cl": hf["moe_latent_size"], "Is": hf["moe_shared_expert_intermediate_size"] * hf["n_shared_experts"],
            "route_scale": float(hf["routed_scaling_factor"]), "eps": hf["norm_eps"], "std": hf["initializer_range"],
            "dt_range": (hf["time_step_min"], hf["time_step_max"]), "dt_floor": hf["time_step_floor"],
            "L_m": kinds.count("mamba2"), "L_e": kinds.count("mlp"), "L_a": kinds.count("full_attention"),
            "L_full": kinds.count("full_attention")}      # the name the paged-attention reader knows


def program_config(hf: dict) -> dict:
    """The published keys under the names ``thunder_tpu.models.llama.Config``
    gives them."""
    s = sizes(hf)
    return dict(name=hf["model_name"], block_size=hf["max_position_embeddings"], vocab_size=s["V"],
                padded_vocab_size=s["V"], n_layer=s["L"], n_head=s["nh"], n_embd=s["C"], head_size=s["hs"],
                n_query_groups=s["ng"], norm_eps=s["eps"], rotary_percentage=0.0, tie_embeddings=False,
                layer_types=s["kinds"], mamba_heads=s["H"], mamba_head_dim=s["P"], mamba_groups=s["G"],
                mamba_state=s["N"], mamba_conv_kernel=s["K"],
                mlp_class="SparseMoE", intermediate_size=s["Im"], n_expert=s["E"], n_expert_per_token=s["k"],
                expert_first=s["first"], expert_held=s["held"], moe_router="sigmoid_bias",
                routed_scaling_factor=s["route_scale"], shared_expert_size=s["Is"], shared_expert_gate=False,
                moe_latent_size=s["Cl"], moe_activation="relu2")


def expert_params(hf: dict) -> int:
    s = sizes(hf)
    return 2 * s["Cl"] * s["Im"]


def layer_params(hf: dict, i: int, held: int | None = None) -> int:
    """Layer ``i``'s parameters with ``held`` of its experts (this share's, where None)."""
    s = sizes(hf)
    C, d = s["C"], s["d"]
    kind = s["kinds"][i]
    if kind == "mamba2":
        body = C * (d + s["W"] + s["H"]) + s["W"] * s["K"] + s["W"] + 3 * s["H"] + d + d * C
    elif kind == "full_attention":
        body = C * s["hs"] * (2 * s["nh"] + 2 * s["ng"])
    else:
        body = (s["E"] * C + s["E"] + 2 * C * s["Is"] + 2 * C * s["Cl"]
                + (s["held"] if held is None else held) * expert_params(hf))
    return body + C


def param_count(hf: dict, held: int | None = None, vocab: int | None = None) -> int:
    """This share's parameters; with ``held`` and ``vocab`` the published counts, the whole model's at this depth."""
    s = sizes(hf)
    return (sum(layer_params(hf, i, held) for i in range(s["L"])) + 2 * (vocab or s["V"]) * s["C"] + s["C"])


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """What the paged cache keeps a token: K and V of the attention layers."""
    s = sizes(hf)
    return 2 * s["L_a"] * s["ng"] * s["hs"] * itemsize


def state_bytes_per_slot(hf: dict, itemsize: int = 2) -> int:
    """The Mamba-2 layers' float32 states and their conv tails."""
    s = sizes(hf)
    return s["L_m"] * (s["d"] * s["N"] * 4 + (s["K"] - 1) * s["W"] * itemsize)


def make_params(hf: dict, seed_words, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed (``common.seed_words``, an argument of the
    caller's ``jit``, not a constant of it), made where that ``jit`` puts them.
    Each leaf's key is folded from its place in the model, so layer ``i`` is the
    same whatever the depth, and a held expert from its number among all the
    experts, so expert ``e`` is the same whatever the share.  Matrices and the
    conv's bias normal of std ``initializer_range``; norms 1; the router's bias
    normal of std ``BIAS_STD`` in float32; ``dt_bias`` the inverse softplus of a
    time step drawn log-uniform in [``time_step_min``, ``time_step_max``] (no less
    than ``time_step_floor``), ``A_log = log U(1, 16)`` a head, ``D = 1``."""
    s = sizes(hf)
    C, d, H, W = s["C"], s["d"], s["H"], s["W"]
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
    for i, kind in enumerate(s["kinds"]):
        block = {"norm_1": ones(C)}
        if kind == "mamba2":
            lo, hi = (math.log(v) for v in s["dt_range"])
            dt = jnp.maximum(jnp.exp(jax.random.uniform(key(13, i), (H,), jnp.float32, lo, hi)), s["dt_floor"])
            block["mamba2"] = {
                "in_proj": dense(0, i, d + W + H, C), "conv_w": dense(1, i, W, s["K"]), "conv_b": dense(2, i, W),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(key(14, i), (H,), jnp.float32, *A_RANGE)),
                "D": jnp.ones((H,), jnp.float32), "norm": ones(d), "out_proj": dense(3, i, C, d)}
        elif kind == "full_attention":
            block["attn"] = {"wq": dense(0, i, s["nh"] * s["hs"], C), "wk": dense(1, i, s["ng"] * s["hs"], C),
                             "wv": dense(2, i, s["ng"] * s["hs"], C), "wo": dense(3, i, C, s["nh"] * s["hs"])}
        else:
            block["mlp"] = {"gate": dense(8, i, s["E"], C),
                            "expert_bias": jax.random.normal(key(12, i), (s["E"],), jnp.float32) * BIAS_STD,
                            "latent_down": dense(4, i, s["Cl"], C), "latent_up": dense(5, i, C, s["Cl"]),
                            "fc_1": experts(9, i, s["Cl"], s["Im"]), "proj": experts(11, i, s["Im"], s["Cl"]),
                            "shared": {"fc_1": dense(6, i, s["Is"], C), "proj": dense(7, i, C, s["Is"])}}
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


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _by_rows(fn, x, *rest):
    """``fn(rows, *rest)`` over blocks of ``ROWS`` rows of ``x``, one block after
    another: the intermediates of one block at a time."""
    T = x.shape[0]
    n = ROWS if T % ROWS == 0 else T
    out = jax.lax.map(lambda a: fn(a, *rest), x.reshape(T // n, n, *x.shape[1:]))
    return out.reshape(T, *out.shape[2:])


def conv_inputs(u, mp, s):
    """``z (T, d)``, the conv's inputs ``xBC (T, d + 2 G N)`` (before the conv) and ``dt (T, H)`` (before its bias)."""
    zxd = _lin(u, mp["in_proj"])
    return zxd[:, :s["d"]], zxd[:, s["d"]:s["d"] + s["W"]], zxd[:, s["d"] + s["W"]:]


def scan(u, mp, s, live=None):
    """A Mamba-2 scan over the whole sequence ``u (T, C)``, a token at a time:
    ``(y (T, d) before the gate, z (T, d), the state after the last token (H, P,
    N))``.  ``live (T, 1)`` bool: the rows past it are padding and must leave the
    state alone: their time step is zero."""
    T, K, H, P, G, N, d = u.shape[0], s["K"], s["H"], s["P"], s["G"], s["N"], s["d"]
    z, xbc, dt = conv_inputs(u, mp, s)
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    w = _f32(mp["conv_w"])
    xbc = jax.nn.silu(sum(padded[j:j + T] * w[:, j] for j in range(K)) + _f32(mp["conv_b"]))
    x = xbc[:, :d].reshape(T, H, P)
    Bm, Cm = xbc[:, d:d + G * N].reshape(T, G, N), xbc[:, d + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + _f32(mp["dt_bias"]))
    if live is not None:
        dt = jnp.where(live, dt, 0.0)
    A = -jnp.exp(_f32(mp["A_log"]))                                       # (H,)
    rep = H // G

    def step(S, a):
        x_t, dt_t, b_t, c_t = a                                           # (H, P), (H,), (G, N), (G, N)
        b_h, c_h = jnp.repeat(b_t, rep, axis=0), jnp.repeat(c_t, rep, axis=0)          # a head's group's
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, c_h)

    S, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (x, dt, Bm, Cm))
    return (y + _f32(mp["D"])[:, None] * x).reshape(T, d), z, S


def _mamba2(u, mp, s):
    y, z, _ = scan(u, mp, s)
    T, G, d = u.shape[0], s["G"], s["d"]
    y = (y * jax.nn.silu(z)).reshape(T, G, d // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + s["eps"])
    return _lin(y.reshape(T, d) * _f32(mp["norm"]), mp["out_proj"])


def keys_values(u, ap, s):
    """What a cache holds of every token: keys and values ``(T, ng, hs)`` each (no rotation)."""
    T = u.shape[0]
    return _lin(u, ap["wk"]).reshape(T, s["ng"], s["hs"]), _lin(u, ap["wv"]).reshape(T, s["ng"], s["hs"])


def _attention(u, ap, s):
    """The full causal score matrix, the query heads of one KV head and
    ``QUERY_ROWS`` queries at a time."""
    T, nh, ng, hs = u.shape[0], s["nh"], s["ng"], s["hs"]
    rows = QUERY_ROWS if T % QUERY_ROWS == 0 else T
    q = _lin(u, ap["wq"]).reshape(T // rows, rows, ng, nh // ng, hs)
    k, v = keys_values(u, ap, s)

    def block(a):
        qb, first = a                                                     # (rows, ng, rep, hs)
        keep = jnp.arange(T)[None, :] <= (first + jnp.arange(rows))[:, None]

        def group(b):
            qg, kg, vg = b                                                # (rows, rep, hs), (T, hs), (T, hs)
            sc = jnp.where(keep[None], jnp.einsum("trh,sh->rts", qg, kg) * hs ** -0.5, -jnp.inf)
            return jnp.einsum("rts,sh->trh", jax.nn.softmax(sc, axis=-1), vg)

        return jax.lax.map(group, (qb.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1))).swapaxes(0, 1)

    o = jax.lax.map(block, (q, jnp.arange(T // rows) * rows))              # (T / rows, rows, ng, rep, hs)
    return _lin(o.reshape(T, nh * hs), ap["wo"])


def route(u, gate, bias, s):
    """The biased choice: ``(weights (T, k), experts (T, k))`` over all E."""
    sc = jax.nn.sigmoid(_lin(u, gate))
    _, idx = jax.lax.top_k(sc + _f32(bias), s["k"])
    w = jnp.take_along_axis(sc, idx, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * s["route_scale"], idx


def routed_latent(u, mp, s):
    """``r (T, Cl)``: the held experts' weighted sum in the latent, by a mask:
    every token through every held expert, one expert at a time, weighted by
    what the router gave it (zero if not chosen)."""
    Cl, Im, held = s["Cl"], s["Im"], s["held"]
    w, idx = route(u, mp["gate"], mp["expert_bias"], s)
    v = _lin(u, mp["latent_down"])
    fc_1, proj = mp["fc_1"].reshape(held, Cl, Im), mp["proj"].reshape(held, Im, Cl)

    def one(r, a):
        e, w1, w2 = a
        we = jnp.sum(jnp.where(idx == s["first"] + e, w, 0.0), axis=-1)                  # (T,)
        rows = lambda vb: _relu2(vb @ _f32(w1)) @ _f32(w2)  # noqa: E731
        return r + we[:, None] * _by_rows(rows, v), None

    return jax.lax.scan(one, jnp.zeros_like(v), (jnp.arange(held), fc_1, proj))[0]


def shared_expert(u, mp):
    return _lin(_relu2(_lin(u, mp["shared"]["fc_1"])), mp["shared"]["proj"])


def _experts(u, mp, s):
    return _lin(routed_latent(u, mp, s), mp["latent_up"]) + shared_expert(u, mp)


def _block(x, bp, s, kind):
    """One layer on ``x (T, C)`` float32: ``x + f(RMSNorm(x))``."""
    u = _rms(x, bp["norm_1"], s["eps"])
    if kind == "mamba2":
        return x + _mamba2(u, bp["mamba2"], s)
    if kind == "full_attention":
        return x + _attention(u, bp["attn"], s)
    return x + _experts(u, bp["mlp"], s)


def _hf_items(hf: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in hf.items() if not isinstance(v, (dict, list))))


@functools.partial(jax.jit, static_argnames=("hf_items", "kind"))
def _ref_block(x, bp, *, hf_items, kind):
    with jax.default_matmul_precision("highest"):
        return _block(x, bp, sizes(dict(hf_items)), kind)


@functools.partial(jax.jit, static_argnames=("hf_items", "kind"))
def _ref_held(x, bp, n_real, *, hf_items, kind):
    """What a cache holds of this layer once the first ``n_real`` tokens of ``x``
    went in.  mamba2: the state ``(d, N)`` after token ``n_real - 1`` (head ``h``'s
    ``(P, N)`` matrix in rows ``[h P, (h + 1) P)``) and the conv's tail ``(K - 1, d +
    2 G N)`` (``xBC`` before the conv of tokens ``n_real - (K - 1) .. n_real - 1``,
    zeros before the first).  An attention layer: every token's key and value
    ``(ng, T, hs)``."""
    with jax.default_matmul_precision("highest"):
        s = sizes(dict(hf_items))
        u = _rms(x, bp["norm_1"], s["eps"])
        if kind == "mamba2":
            _, _, S = scan(u, bp["mamba2"], s, live=(jnp.arange(x.shape[0]) < n_real)[:, None])
            _, xbc, _ = conv_inputs(u, bp["mamba2"], s)
            tail = jax.lax.dynamic_slice_in_dim(jnp.pad(xbc, ((s["K"] - 1, 0), (0, 0))), n_real, s["K"] - 1, axis=0)
            return S.reshape(s["d"], s["N"]), tail
        return tuple(a.swapaxes(0, 1) for a in keys_values(u, bp["attn"], s))


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
    """The layers in order on one sequence; ``visit(kind, x before the layer, block)`` before each."""
    s, items = sizes(hf), _hf_items(hf)
    x = params["wte"][tokens].astype(jnp.float32)
    for kind, bp in zip(s["kinds"], params["blocks"]):
        if visit is not None:
            visit(kind, x, bp)
        x = _ref_block(x, bp, hf_items=items, kind=kind)
        # a layer at a time on the device too: a call's results are allocated when it is enqueued
        jax.block_until_ready(x)
    return x


def ref_hidden(hf: dict, params: dict, tokens):
    """Final hidden states ``(T, C)`` before the last norm, for one sequence of
    token ids ``(T,)``.  One layer's weights are upcast at a time, an expert's
    inside it."""
    return _walk(hf, params, tokens)


def longest_memory_rows(hf: dict, mp: dict, share: int = 8):
    """The rows of a Mamba-2 layer's state ``(d, N)`` that belong to the ``H /
    share`` heads (one at least) which forget slowest: the smallest
    ``softplus(dt_bias) exp(A_log)``, a head's decay rate a token at an input of
    zero, from the layer's own parameters ``mp``.  Sorted; numpy."""
    import numpy as np

    s = sizes(hf)
    rate = np.logaddexp(0.0, np.asarray(mp["dt_bias"], np.float64)) * np.exp(np.asarray(mp["A_log"], np.float64))
    heads = np.sort(np.argsort(rate, kind="stable")[:max(1, s["H"] // share)])
    return (heads[:, None] * s["P"] + np.arange(s["P"])).reshape(-1)


def ref_caches(hf: dict, params: dict, tokens, n_real: int):
    """What a server's caches hold of one sequence after its first ``n_real``
    tokens (the rest of ``tokens`` is padding), by the kind of each layer, in the
    model's order, all float32: ``("mamba2", (state (d, N), tail (K - 1, d + 2 G
    N)))`` for a Mamba-2 layer (the server holds the state ``(N, d)``, the
    channels on the chip's lanes: ``drivers/serve_rows.py`` turns it for the
    comparison); ``("full_attention", (k, v))``, each ``(ng, n_real, hs)``;
    ``("mlp", None)`` for an expert layer, which keeps nothing.  A list, made a
    layer at a time."""
    items, out = _hf_items(hf), []

    def visit(kind, x, bp):
        if kind == "mlp":
            out.append((kind, None))
            return
        got = jax.device_get(_ref_held(x, bp, n_real, hf_items=items, kind=kind))
        out.append((kind, got if kind == "mamba2" else tuple(a[:, :n_real] for a in got)))

    _walk(hf, params, tokens, visit)
    return out


def ref_logits(hf: dict, params: dict, tokens, positions):
    """Logits ``(len(positions), V)`` of the full forward pass at the given
    positions of one sequence, over this share's slice of the vocabulary."""
    x = ref_hidden(hf, params, tokens)
    return _ref_logits(x[positions], params["ln_f"], params["lm_head"], eps=hf["norm_eps"])
