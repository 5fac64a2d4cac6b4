"""A hybrid dense decoder as Olmo-Hybrid-7B's public ``config.json`` describes
it (``model_type: olmo_hybrid``): layers of two kinds in a fixed pattern
(``layer_types``: three ``linear_attention`` to one ``full_attention``), a dense
SwiGLU feed-forward in every block, the OLMo family's block (norms on the
sublayers' *outputs*), no rotary embedding.  This file is the benchmark's own
copy of the architecture: the weights it makes from the seed, the plain
float32 reference (the recurrence token by token: no chunks, no cache, no
kernel), and the sizes the per-layer readers count from.  It reads the
published keys of the configuration file and imports nothing of the program
under test.

The equations (``d`` = hidden size, RMSNorm eps from the config, no bias
anywhere, untied head).  ``RMSNorm_n(x) = x / sqrt(mean_n(x^2) + eps) * w``.

*Linear-attention layer* (``nk`` key heads and ``nv`` value heads of ``dk`` and
``dv``; conv width ``K``), per token ``t`` and value head (its key head is ``h
// (nv / nk)``)::

    [q|k|v]_t = SiLU(conv_K([W_q|W_k|W_v] x)_t)      causal, depthwise, no bias:
                conv_K(u)_t = sum_j w[:, j] * u_{t - (K - 1) + j}, u_s = 0 for s < 0
    z_t = W_z x_t,   b_t = W_b x_t,   a_t = W_a x_t
    qh = q / sqrt(|q|^2 + 1e-6) * dk^-1/2,   kh = k / sqrt(|k|^2 + 1e-6)
    alpha_t = exp(-exp(A_log) * softplus(a_t + dt_bias))                in (0, 1)
    beta_t  = 2 * sigmoid(b_t)    (``linear_allow_neg_eigval``; sigmoid(b_t) without):
              the transition alpha (I - beta kh kh^T) has the eigenvalue
              alpha (1 - beta) in (-1, 1) along kh
    S_t = alpha_t S_{t-1} + kh_t (beta_t (v_t - alpha_t S_{t-1}^T kh_t))^T
              S in float32, (dk, dv), S_0 = 0
    o_t = S_t^T qh_t
    y_t = W_o (RMSNorm_dv(o_t) * SiLU(z_t))

*Full-attention layer* (``nh`` heads and ``ng`` KV heads of ``hs``)::

    q = RMSNorm_{nh hs}(W_q x),  k = RMSNorm_{ng hs}(W_k x)     over the whole
        projection, then split into heads;   v = W_v x
    no rotary embedding (``rope_theta`` is null in the published config)
    y = W_o softmax_causal(q k^T * hs^-1/2) v

*Block*: ``h = x + RMSNorm(Mixer(x))``, ``out = h + RMSNorm(W_down(SiLU(W_gate
h) * W_up h))``; after the last block one more RMSNorm, then the head.

Weight layout (what the program is handed; a linear layer is ``x @ W.T``):
``wte (V, C)``, ``lm_head (V, C)``, ``ln_f (C)``; a block: ``norm_1``, ``norm_2
(C)`` (the norms of the mixer's and of the feed-forward's output), ``mlp.fc_1/
fc_2 (I, C)`` (gate, up), ``mlp.proj (C, I)``; a full-attention block:
``attn.wq (nh hs, C)``, ``attn.wk/wv (ng hs, C)``, ``attn.wo (C, nh hs)``,
``attn.q_norm (nh hs)``, ``attn.k_norm (ng hs)``; a linear-attention block:
``gdn.in_proj_qkvz (2 nk dk + 2 nv dv, C)`` packed ``[q | k | v | z]``,
``gdn.in_proj_ba (2 nv, C)`` packed ``[b | a]``, ``gdn.conv_w (2 nk dk + nv
dv, K)``, ``gdn.A_log``, ``gdn.dt_bias (nv)``, ``gdn.norm (dv)``,
``gdn.out_proj (C, nv dv)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.common import seed_key

OUTSIDE_BLOCKS = 0xFFFF   # the place folded into the keys of the embedding and the head
ROWS = 512                # rows of the feed-forward and of attention's queries at a time


def sizes(hf: dict) -> dict:
    nh, L = hf["num_attention_heads"], hf["num_hidden_layers"]
    kinds = tuple(hf["layer_types"][:L])      # the published pattern, as deep as this cut
    return {"C": hf["hidden_size"], "nh": nh, "ng": hf["num_key_value_heads"],
            "hs": hf.get("head_dim") or hf["hidden_size"] // nh,
            "I": hf["intermediate_size"], "V": hf["vocab_size"], "L": L, "kinds": kinds,
            "L_full": kinds.count("full_attention"), "L_lin": kinds.count("linear_attention"),
            "nk": hf["linear_num_key_heads"], "nv": hf["linear_num_value_heads"],
            "dk": hf["linear_key_head_dim"], "dv": hf["linear_value_head_dim"],
            "K": hf["linear_conv_kernel_dim"], "neg": bool(hf.get("linear_allow_neg_eigval", False)),
            "eps": hf["rms_norm_eps"], "std": hf["initializer_range"]}


def program_config(hf: dict) -> dict:
    """The published keys under the names ``thunder_tpu.models.llama.Config``
    gives them."""
    s = sizes(hf)
    return dict(name=hf["model_name"], block_size=hf["max_position_embeddings"],
                vocab_size=s["V"], n_layer=s["L"], n_head=s["nh"], n_embd=s["C"],
                head_size=s["hs"], n_query_groups=s["ng"], intermediate_size=s["I"],
                norm_eps=s["eps"], rotary_percentage=0.0, layer_types=s["kinds"],
                post_sublayer_norm=True, qk_norm_whole=True,
                linear_num_key_heads=s["nk"], linear_num_value_heads=s["nv"],
                linear_key_head_dim=s["dk"], linear_value_head_dim=s["dv"],
                linear_conv_kernel=s["K"], linear_allow_neg_eigval=s["neg"])


def layer_params(hf: dict, kind: str) -> int:
    s = sizes(hf)
    mlp_and_norms = 3 * s["C"] * s["I"] + 2 * s["C"]
    if kind == "linear_attention":
        n_qkv = 2 * s["nk"] * s["dk"] + s["nv"] * s["dv"]
        mixer = (s["C"] * (n_qkv + s["nv"] * s["dv"] + 2 * s["nv"]) + n_qkv * s["K"]
                 + 2 * s["nv"] + s["dv"] + s["nv"] * s["dv"] * s["C"])
    else:
        mixer = s["C"] * s["hs"] * (2 * s["nh"] + 2 * s["ng"]) + s["hs"] * (s["nh"] + s["ng"])
    return mixer + mlp_and_norms


def param_count(hf: dict) -> int:
    s = sizes(hf)
    return sum(layer_params(hf, k) for k in s["kinds"]) + 2 * s["V"] * s["C"] + s["C"]


def state_bytes_per_slot(hf: dict, state_itemsize: int = 4, conv_itemsize: int = 2) -> int:
    """What one request keeps beside its KV: the delta rule's states and the conv's last inputs."""
    s = sizes(hf)
    n_qkv = 2 * s["nk"] * s["dk"] + s["nv"] * s["dv"]
    return s["L_lin"] * (s["nv"] * s["dk"] * s["dv"] * state_itemsize + (s["K"] - 1) * n_qkv * conv_itemsize)


DT_RANGE = (1e-3, 1e-1)


def _decay(key_a, key_dt, heads: int, dtype) -> dict:
    """``A_log`` and ``dt_bias`` of one layer's heads, drawn as the layer that
    trains these models draws them (flash-linear-attention's ``GatedDeltaNet``,
    after Mamba-2): ``A = U(0, 16)``, ``dt`` log-uniform in ``DT_RANGE``,
    ``dt_bias = softplus^-1(dt)``.  A head then forgets at ``alpha = exp(-A dt)``
    a token: half of them remember over ten tokens or more, one in seven over a
    hundred, so the state integrates a context as a trained model's does.
    (hf's ``dt_bias = 1`` is a placeholder that a checkpoint overwrites: with it
    nine heads in ten forget within one token, and the state is one rank-one
    term whose storage no comparison can see.)"""
    A = jax.random.uniform(key_a, (heads,), jnp.float32, 1e-3, 16.0)
    lo, hi = (jnp.log(jnp.float32(v)) for v in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(key_dt, (heads,), jnp.float32, lo, hi))
    return {"A_log": jnp.log(A).astype(dtype), "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)}


def make_params(hf: dict, seed_words, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed (``common.seed_words``, an argument of the
    caller's ``jit``, not a constant of it), made where that ``jit`` puts
    them.  Each leaf's key is folded from its place in the model, so layer
    ``i`` is the same whatever the depth.  Matrices normal of std
    ``initializer_range``; norms 1; the decay as the Gated DeltaNet's own layer
    draws it (``_decay``)."""
    s = sizes(hf)
    root = seed_key(seed_words)
    key = lambda tag, layer: jax.random.fold_in(jax.random.fold_in(root, layer), tag)  # noqa: E731

    def dense(tag: int, layer: int, fan_out: int, fan_in: int):
        return (jax.random.normal(key(tag, layer), (fan_out, fan_in), jnp.float32) * s["std"]).astype(dtype)

    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    n_qkv = 2 * s["nk"] * s["dk"] + s["nv"] * s["dv"]
    blocks = []
    for i, kind in enumerate(s["kinds"]):
        block = {"norm_1": ones(s["C"]), "norm_2": ones(s["C"]),
                 "mlp": {"fc_1": dense(4, i, s["I"], s["C"]), "fc_2": dense(5, i, s["I"], s["C"]),
                         "proj": dense(6, i, s["C"], s["I"])}}
        if kind == "linear_attention":
            block["gdn"] = {
                "in_proj_qkvz": dense(0, i, n_qkv + s["nv"] * s["dv"], s["C"]),
                "in_proj_ba": dense(1, i, 2 * s["nv"], s["C"]),
                "conv_w": dense(2, i, n_qkv, s["K"]),
                **_decay(key(7, i), key(8, i), s["nv"], dtype), "norm": ones(s["dv"]),
                "out_proj": dense(3, i, s["C"], s["nv"] * s["dv"])}
        else:
            block["attn"] = {"wq": dense(0, i, s["nh"] * s["hs"], s["C"]), "wk": dense(1, i, s["ng"] * s["hs"], s["C"]),
                             "wv": dense(2, i, s["ng"] * s["hs"], s["C"]), "wo": dense(3, i, s["C"], s["nh"] * s["hs"]),
                             "q_norm": ones(s["nh"] * s["hs"]), "k_norm": ones(s["ng"] * s["hs"])}
        blocks.append(block)
    return {"wte": dense(0, OUTSIDE_BLOCKS, s["V"], s["C"]), "blocks": blocks,
            "ln_f": ones(s["C"]), "lm_head": dense(1, OUTSIDE_BLOCKS, s["V"], s["C"])}


# --------------------------------------------------------------------------
# The plain reference: float32, highest matmul precision, no kernel, no cache
# --------------------------------------------------------------------------

def _lin(x, w):
    return x @ w.astype(jnp.float32).T


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _by_rows(fn, x, *rest):
    """``fn(rows, first_row, *rest)`` over blocks of ``ROWS`` rows of ``x``, one
    block after another: the intermediates of one block at a time."""
    T = x.shape[0]
    n = ROWS if T % ROWS == 0 else T
    blocks = x.reshape(T // n, n, *x.shape[1:])
    out = jax.lax.map(lambda a: fn(a[0], a[1], *rest), (blocks, jnp.arange(T // n) * n))
    return out.reshape(T, *out.shape[2:])


def _attend(qb, q0, k, v):
    """Causal softmax attention of a block of queries ``(t, ng, rep, hs)``, the
    first at position ``q0``, over all keys."""
    keep = jnp.arange(k.shape[0])[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None]
    sc = jnp.einsum("tgrh,sgh->grts", qb, k) / (qb.shape[-1] ** 0.5)
    sc = jnp.where(keep[None, None], sc, -jnp.inf)
    return jnp.einsum("grts,sgh->tgrh", jax.nn.softmax(sc, axis=-1), v)


def _keys_values(x, ap, s):
    T = x.shape[0]
    k = _rms(_lin(x, ap["wk"]), ap["k_norm"], s["eps"]).reshape(T, s["ng"], s["hs"])
    return k, _lin(x, ap["wv"]).reshape(T, s["ng"], s["hs"])


def _full_attention(x, ap, s):
    T = x.shape[0]
    q = _rms(_lin(x, ap["wq"]), ap["q_norm"], s["eps"]).reshape(T, s["ng"], s["nh"] // s["ng"], s["hs"])
    k, v = _keys_values(x, ap, s)
    return _lin(_by_rows(_attend, q, k, v).reshape(T, s["nh"] * s["hs"]), ap["wo"])


def _delta_rule(q, k, v, alpha, beta):
    """The gated delta rule token by token.  q, k ``(T, nv, dk)``, v ``(T, nv,
    dv)``, alpha, beta ``(T, nv)`` -> the last ``S (nv, dk, dv)``, from zero, and
    the read-outs ``(T, nv, dv)``."""
    def token(S, x):
        qt, kt, vt, at, bt = x
        S = S * at[:, None, None]
        d = (vt - jnp.einsum("hkd,hk->hd", S, kt)) * bt[:, None]
        S = S + kt[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkd,hk->hd", S, qt)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(token, S0, (q, k, v, alpha, beta))


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _delta_inputs(x, gp, s):
    """What the recurrence takes a token, and the read-out's gate: q, k ``(T,
    nv, dk)``, v, z ``(T, nv, dv)``, alpha, beta ``(T, nv)``."""
    T = x.shape[0]
    nk, nv, dk, dv, K = s["nk"], s["nv"], s["dk"], s["dv"], s["K"]
    n_qkv = 2 * nk * dk + nv * dv
    qkvz, ba = _lin(x, gp["in_proj_qkvz"]), _lin(x, gp["in_proj_ba"])
    z = qkvz[:, n_qkv:]
    padded = jnp.pad(qkvz[:, :n_qkv], ((K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j:j + T] * gp["conv_w"].astype(jnp.float32)[:, j] for j in range(K)))
    q = qkv[:, :nk * dk].reshape(T, nk, dk)
    k = qkv[:, nk * dk:2 * nk * dk].reshape(T, nk, dk)
    v = qkv[:, 2 * nk * dk:].reshape(T, nv, dv)
    q = jnp.repeat(_l2norm(q) * dk ** -0.5, nv // nk, axis=1)    # value head h reads key head h // (nv / nk)
    k = jnp.repeat(_l2norm(k), nv // nk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :nv]) * (2.0 if s["neg"] else 1.0)
    alpha = jnp.exp(-jnp.exp(gp["A_log"].astype(jnp.float32))
                    * jax.nn.softplus(ba[:, nv:] + gp["dt_bias"].astype(jnp.float32)))
    return q, k, v, alpha, beta, z.reshape(T, nv, dv)


def _gated_delta_net(x, gp, s):
    q, k, v, alpha, beta, z = _delta_inputs(x, gp, s)
    o = _rms(_delta_rule(q, k, v, alpha, beta)[1], gp["norm"], s["eps"]) * jax.nn.silu(z)
    return _lin(o.reshape(x.shape[0], -1), gp["out_proj"])


def _mlp(h, _, mp):
    return _lin(jax.nn.silu(_lin(h, mp["fc_1"])) * _lin(h, mp["fc_2"]), mp["proj"])


def _block(x, bp, s, kind):
    """One block on ``x (T, C)`` float32: the norms on what the sublayers give."""
    mixed = _gated_delta_net(x, bp["gdn"], s) if kind == "linear_attention" else _full_attention(x, bp["attn"], s)
    h = x + _rms(mixed, bp["norm_1"], s["eps"])
    return h + _rms(_by_rows(_mlp, h, bp["mlp"]), bp["norm_2"], s["eps"])


@functools.partial(jax.jit, static_argnames=("hf_items", "kind"))
def _ref_block(x, bp, *, hf_items, kind):
    with jax.default_matmul_precision("highest"):
        return _block(x, bp, sizes(dict(hf_items)), kind)


@functools.partial(jax.jit, static_argnames=("hf_items", "kind"))
def _ref_held(x, bp, n_real, *, hf_items, kind):
    """What a cache holds of this layer once the first ``n_real`` tokens of
    ``x`` went in: the delta rule's state ``(nv, dk, dv)``, or every token's
    key and value ``(ng, T, hs)``."""
    with jax.default_matmul_precision("highest"):
        s = sizes(dict(hf_items))
        if kind != "linear_attention":
            return tuple(a.swapaxes(0, 1) for a in _keys_values(x, bp["attn"], s))
        q, k, v, alpha, beta, _ = _delta_inputs(x, bp["gdn"], s)
        # a token past the last real one leaves the state as it is: alpha 1, beta 0
        real = (jnp.arange(x.shape[0]) < n_real)[:, None]
        return _delta_rule(q, k, v, jnp.where(real, alpha, 1.0), jnp.where(real, beta, 0.0))[0]


@functools.partial(jax.jit, static_argnames=("eps",))
def _ref_logits(x, ln_f, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, ln_f, eps) @ head.astype(jnp.float32).T


def _hf_items(hf: dict) -> tuple:
    keep = lambda v: tuple(v) if isinstance(v, list) and all(isinstance(e, str) for e in v) else v  # noqa: E731
    return tuple(sorted((k, keep(v)) for k, v in hf.items()
                        if not isinstance(keep(v), (dict, list))))


def ref_hidden(hf: dict, params: dict, tokens):
    """Final hidden states ``(T, C)`` before the last norm, for one sequence
    of token ids ``(T,)``.  One block's weights are upcast at a time."""
    items = _hf_items(hf)
    x = params["wte"][tokens].astype(jnp.float32)
    for bp, kind in zip(params["blocks"], sizes(hf)["kinds"]):
        x = _ref_block(x, bp, hf_items=items, kind=kind)
    return x


def ref_caches(hf: dict, params: dict, tokens, n_real: int):
    """What a server's caches hold of one sequence after its first ``n_real``
    tokens (the rest of ``tokens`` is padding), a layer at a time, in the
    model's order: ``("state", S (nv, dk, dv))`` for a linear_attention layer,
    ``("kv", (k, v))``, each ``(ng, n_real, hs)``, for a full_attention layer,
    all float32.  A generator: one layer's arrays live at a time."""
    items = _hf_items(hf)
    x = params["wte"][tokens].astype(jnp.float32)
    for bp, kind in zip(params["blocks"], sizes(hf)["kinds"]):
        got = _ref_held(x, bp, n_real, hf_items=items, kind=kind)
        yield ("state", got) if kind == "linear_attention" else ("kv", tuple(a[:, :n_real] for a in got))
        del got
        x = _ref_block(x, bp, hf_items=items, kind=kind)


def ref_logits(hf: dict, params: dict, tokens, positions):
    """Logits ``(len(positions), V)`` of the full forward pass at the given
    positions of one sequence."""
    x = ref_hidden(hf, params, tokens)
    return _ref_logits(x[positions], params["ln_f"], params["lm_head"], eps=hf["rms_norm_eps"])
