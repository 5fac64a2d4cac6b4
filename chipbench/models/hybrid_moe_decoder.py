"""A hybrid sparse decoder as Qwen3-Next's public ``config.json`` and the
Hugging Face ``modeling_qwen3_next.py`` describe it: every fourth layer a
gated softmax attention (q/k RMSNorm a head, partial rotary embedding, a
sigmoid output gate), the others Gated DeltaNet mixers (causal depthwise
conv, L2-normalised q and k, a decayed delta-rule state a head, a gated
RMSNorm of the read-out); every layer a mixture of experts (float32 softmax
router over all experts, top-k renormalised, SwiGLU experts, a shared expert
behind a sigmoid gate); zero-centred RMSNorm (scale ``1 + w``); untied head.
This file is the benchmark's own copy of the architecture: the weights it
makes from the seed, the plain float32 reference (the recurrence token by
token, the experts by a mask), and the count of operations a token needs.
It reads the published keys of the configuration file and imports nothing
of the program under test.

**The chip's share.**  ``num_experts`` counts the experts *held here*
(experts ``0 .. num_experts - 1`` of ``published_num_experts``); the router
stays ``published_num_experts`` wide and picks ``num_experts_per_tok`` among
all of them; what the absent experts would add is left out, here as in the
program.  ``vocab_size`` is the slice of the vocabulary held here.

**Departures from the published model**, each also under ``assumed`` in the
configuration file: no multi-token-prediction head (the config has no key
for it); no router loss (hf's default, ``output_router_logits`` false); the
fused projections are packed ``[q | k | v | z]`` and ``[b | a]`` over all
heads, and ``q_proj`` ``[q | gate]`` a head (hf groups ``qkvz`` by key head:
immaterial with random weights); expert weights are stored for ``x @ W``.

Weight layout (what the program is handed): ``wte``, ``lm_head (V, C)``,
``ln_f (C)``; per block ``norm_1``, ``norm_2 (C)``; a linear-attention block
has ``gdn.in_proj_qkvz (2 nk dk + 2 nv dv, C)``, ``gdn.in_proj_ba (2 nv,
C)``, ``gdn.conv_w (2 nk dk + nv dv, K)``, ``gdn.A_log``, ``gdn.dt_bias
(nv)``, ``gdn.norm (dv)``, ``gdn.out_proj (C, nv dv)``; a full-attention
block ``attn.wq (2 nh hs, C)``, ``attn.wk/wv (ng hs, C)``, ``attn.wo (C, nh
hs)``, ``attn.q_norm/k_norm (hs)``; every block ``mlp.gate (E_all, C)``,
``mlp.fc_1/fc_2 (E_held * C, I)``, ``mlp.proj (E_held * I, C)`` and
``mlp.shared.fc_1/fc_2 (Is, C)``, ``.proj (C, Is)``, ``.gate (1, C)``.  A
linear layer is ``x @ W.T``; an expert is ``(silu(x @ fc_1[e]) * (x @
fc_2[e])) @ proj[e]`` with ``fc_1[e]`` rows ``e*C .. (e+1)*C``.  The expert
leaves are two-dimensional so that the driver's sample of a gradient stays
256 rows of each.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.common import seed_key

OUTSIDE_BLOCKS = 0xFFFF   # the place folded into the keys of the embedding and the head
GDN_CHUNK = 64            # the chunk of the chunked algorithm, for the count of operations only


def sizes(hf: dict) -> dict:
    nh = hf["num_attention_heads"]
    return {"C": hf["hidden_size"], "nh": nh, "ng": hf["num_key_value_heads"], "hs": hf["head_dim"],
            "rot": int(hf["partial_rotary_factor"] * hf["head_dim"]),
            "nk": hf["linear_num_key_heads"], "nv": hf["linear_num_value_heads"],
            "dk": hf["linear_key_head_dim"], "dv": hf["linear_value_head_dim"],
            "K": hf["linear_conv_kernel_dim"],
            "I": hf["moe_intermediate_size"], "Is": hf["shared_expert_intermediate_size"],
            "E": hf["num_experts"], "E_all": hf.get("published_num_experts", hf["num_experts"]),
            "first": hf.get("first_expert", 0), "k": hf["num_experts_per_tok"],
            "V": hf["vocab_size"], "L": hf["num_hidden_layers"],
            "interval": hf["full_attention_interval"],
            "theta": float(hf["rope_theta"]), "eps": hf["rms_norm_eps"], "std": hf["initializer_range"]}


def layer_types(hf: dict) -> tuple:
    n = hf["full_attention_interval"]
    return tuple("full_attention" if (i + 1) % n == 0 else "linear_attention"
                 for i in range(hf["num_hidden_layers"]))


def program_config(hf: dict) -> dict:
    """The published keys under the names ``thunder_tpu.models.llama.Config``
    gives them."""
    s = sizes(hf)
    return dict(name=hf["model_name"], block_size=hf["max_position_embeddings"],
                vocab_size=s["V"], padded_vocab_size=s["V"], n_layer=s["L"], n_head=s["nh"],
                n_embd=s["C"], head_size=s["hs"], n_query_groups=s["ng"],
                rotary_percentage=hf["partial_rotary_factor"], rope_base=int(s["theta"]),
                norm_eps=s["eps"], norm_zero_centered=True, qk_norm=True, attn_output_gate=True,
                layer_types=layer_types(hf),
                linear_num_key_heads=s["nk"], linear_num_value_heads=s["nv"],
                linear_key_head_dim=s["dk"], linear_value_head_dim=s["dv"], linear_conv_kernel=s["K"],
                mlp_class="SparseMoE", intermediate_size=s["I"], n_expert=s["E_all"],
                n_expert_per_token=s["k"], expert_first=s["first"], expert_held=s["E"],
                shared_expert_size=s["Is"])


def mixer_params(hf: dict, kind: str) -> int:
    s = sizes(hf)
    if kind == "linear_attention":
        n_qkv = 2 * s["nk"] * s["dk"] + s["nv"] * s["dv"]
        return (s["C"] * (n_qkv + s["nv"] * s["dv"]) + s["C"] * 2 * s["nv"] + n_qkv * s["K"]
                + 2 * s["nv"] + s["dv"] + s["nv"] * s["dv"] * s["C"])
    return s["C"] * s["hs"] * (3 * s["nh"] + 2 * s["ng"]) + 2 * s["hs"]


def expert_params(hf: dict) -> int:
    s = sizes(hf)
    return 3 * s["C"] * s["I"]


def layer_params(hf: dict, kind: str) -> int:
    s = sizes(hf)
    outside = s["E_all"] * s["C"] + 3 * s["C"] * s["Is"] + s["C"] + 2 * s["C"]
    return mixer_params(hf, kind) + outside + s["E"] * expert_params(hf)


def param_count(hf: dict) -> int:
    s = sizes(hf)
    return sum(layer_params(hf, k) for k in layer_types(hf)) + 2 * s["V"] * s["C"] + s["C"]


def make_params(hf: dict, seed_words, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed (``common.seed_words``, an argument of the
    caller's ``jit``).  Matrices are normal with the published
    ``initializer_range``; norm weights, ``A_log`` and ``dt_bias`` start as
    hf initialises them (zero-centred norms 0, the read-out's norm 1, ``A_log
    = log U(0, 16)``, ``dt_bias = 1``).  Each leaf's key is folded from its
    place in the model, so layer ``i`` is the same whatever the depth."""
    s = sizes(hf)
    root = seed_key(seed_words)

    def key(tag: int, layer: int):
        return jax.random.fold_in(jax.random.fold_in(root, layer), tag)

    def dense(tag: int, layer: int, fan_out: int, fan_in: int):
        return (jax.random.normal(key(tag, layer), (fan_out, fan_in), jnp.float32) * s["std"]).astype(dtype)

    zeros = lambda n: jnp.zeros((n,), dtype)  # noqa: E731
    n_qkv = 2 * s["nk"] * s["dk"] + s["nv"] * s["dv"]
    blocks = []
    for i, kind in enumerate(layer_types(hf)):
        block = {"norm_1": zeros(s["C"]), "norm_2": zeros(s["C"])}
        if kind == "linear_attention":
            block["gdn"] = {
                "in_proj_qkvz": dense(0, i, n_qkv + s["nv"] * s["dv"], s["C"]),
                "in_proj_ba": dense(1, i, 2 * s["nv"], s["C"]),
                "conv_w": dense(2, i, n_qkv, s["K"]),
                "A_log": jnp.log(jax.random.uniform(key(3, i), (s["nv"],), jnp.float32, 1e-3, 16.0)).astype(dtype),
                "dt_bias": jnp.ones((s["nv"],), dtype),
                "norm": jnp.ones((s["dv"],), dtype),
                "out_proj": dense(4, i, s["C"], s["nv"] * s["dv"]),
            }
        else:
            block["attn"] = {"wq": dense(0, i, 2 * s["nh"] * s["hs"], s["C"]),
                             "wk": dense(1, i, s["ng"] * s["hs"], s["C"]),
                             "wv": dense(2, i, s["ng"] * s["hs"], s["C"]),
                             "wo": dense(3, i, s["C"], s["nh"] * s["hs"]),
                             "q_norm": zeros(s["hs"]), "k_norm": zeros(s["hs"])}
        block["mlp"] = {
            "gate": dense(5, i, s["E_all"], s["C"]),
            "fc_1": dense(6, i, s["E"] * s["C"], s["I"]),
            "fc_2": dense(7, i, s["E"] * s["C"], s["I"]),
            "proj": dense(8, i, s["E"] * s["I"], s["C"]),
            "shared": {"fc_1": dense(9, i, s["Is"], s["C"]), "fc_2": dense(10, i, s["Is"], s["C"]),
                       "proj": dense(11, i, s["C"], s["Is"]), "gate": dense(12, i, 1, s["C"])},
        }
        blocks.append(block)
    return {"wte": dense(0, OUTSIDE_BLOCKS, s["V"], s["C"]), "blocks": blocks,
            "ln_f": zeros(s["C"]), "lm_head": dense(1, OUTSIDE_BLOCKS, s["V"], s["C"])}


def rope_tables(hf: dict, seq_len: int):
    """cos and sin ``(T, rot)`` in float32 for the rotated part of a head
    (``partial_rotary_factor * head_dim`` dims), rotate-half layout."""
    s = sizes(hf)
    inv = 1.0 / (s["theta"] ** (jnp.arange(0, s["rot"], 2, dtype=jnp.float32) / s["rot"]))
    ang = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


# --------------------------------------------------------------------------
# Operations a token needs (forward and backward; recomputation not counted)
# --------------------------------------------------------------------------

def gdn_scan_macs_per_token(hf: dict) -> float:
    """Multiply-adds a token of the chunked gated delta rule, forward, over
    all value heads (``chipbench/kernels/gdn_chunk.py`` counts the same)."""
    s = sizes(hf)
    C, dk, dv = GDN_CHUNK, s["dk"], s["dv"]
    a_chunk = C * C * (3 * dk + 2 * dv) + 3 * C * dk * dv + C ** 3 / 3.0
    return s["nv"] * a_chunk / C


def forward_macs_per_token(hf: dict, seq_len: int) -> dict:
    """Multiply-adds of one token's forward pass, by part: the matrices it
    passes through (the embedding is a lookup; of the routed experts the
    ``num_experts_per_tok * held / all`` it meets here on average), the
    conv, the scan and the attention over the keys it attends."""
    s = sizes(hf)
    kinds = layer_types(hf)
    n_lin, n_full = kinds.count("linear_attention"), kinds.count("full_attention")
    n_qkv = 2 * s["nk"] * s["dk"] + s["nv"] * s["dv"]
    gdn = n_lin * (s["C"] * (n_qkv + s["nv"] * s["dv"] + 2 * s["nv"]) + n_qkv * s["K"]
                   + s["nv"] * s["dv"] * s["C"])
    attn = n_full * (s["C"] * s["hs"] * (3 * s["nh"] + 2 * s["ng"])
                     + 2 * s["nh"] * s["hs"] * (seq_len + 1) / 2.0)
    rows = s["k"] * s["E"] / s["E_all"]
    experts = s["L"] * rows * expert_params(hf)
    outside = s["L"] * (s["E_all"] * s["C"] + 3 * s["C"] * s["Is"] + s["C"])
    return {"gdn_mixers": gdn, "gdn_scan": n_lin * gdn_scan_macs_per_token(hf), "attention": attn,
            "experts": experts, "router_shared": outside, "head": s["V"] * s["C"]}


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """Two operations a multiply-add, forward once and backward twice."""
    return 6.0 * sum(forward_macs_per_token(hf, seq_len).values())


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
    return _lin_fp8(x, w) if q else x @ w.T


def _rms(x, w, eps, *, centered: bool):
    scale = 1.0 + w if centered else w
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate(x, cos, sin):
    """Rotary embedding of the first ``cos.shape[-1]`` dims of a head."""
    rot = cos.shape[-1]
    xr, rest = x[..., :rot], x[..., rot:]
    half = rot // 2
    xr = xr * cos + jnp.concatenate([-xr[..., half:], xr[..., :half]], axis=-1) * sin
    return jnp.concatenate([xr, rest], axis=-1)


ROWS = 512


def _by_rows(fn, x, *rest):
    """``fn(rows, first_row, *rest)`` over blocks of ``ROWS`` rows of ``x``, one
    block after another and each under ``jax.checkpoint``."""
    T = x.shape[0]
    n = ROWS if T % ROWS == 0 else T
    blocks = x.reshape(T // n, n, *x.shape[1:])
    out = jax.lax.map(lambda a: jax.checkpoint(fn)(a[0], a[1], *rest),
                      (blocks, jnp.arange(T // n) * n))
    return out.reshape(T, *out.shape[2:])


def _attend(qb, q0, k, v):
    """Causal softmax attention of a block of queries ``(t, ng, rep, hs)``,
    the first at position ``q0``, over all keys."""
    kpos = jnp.arange(k.shape[0])
    qpos = q0 + jnp.arange(qb.shape[0])
    sc = jnp.einsum("tgrh,sgh->grts", qb, k) / (qb.shape[-1] ** 0.5)
    sc = jnp.where((kpos[None, :] <= qpos[:, None])[None, None], sc, -jnp.inf)
    return jnp.einsum("grts,sgh->tgrh", jax.nn.softmax(sc, axis=-1), v)


def _gated_attention(h, ap, cos, sin, s, quant):
    T = h.shape[0]
    qg = _lin(h, ap["wq"], quant).reshape(T, s["nh"], 2 * s["hs"])
    q, gate = qg[..., :s["hs"]], qg[..., s["hs"]:]
    k = _lin(h, ap["wk"], quant).reshape(T, s["ng"], s["hs"])
    v = _lin(h, ap["wv"], quant).reshape(T, s["ng"], s["hs"])
    q = _rotate(_rms(q, ap["q_norm"], s["eps"], centered=True), cos[:, None], sin[:, None])
    k = _rotate(_rms(k, ap["k_norm"], s["eps"], centered=True), cos[:, None], sin[:, None])
    y = _by_rows(_attend, q.reshape(T, s["ng"], s["nh"] // s["ng"], s["hs"]), k, v)
    y = y.reshape(T, s["nh"] * s["hs"]) * jax.nn.sigmoid(gate.reshape(T, s["nh"] * s["hs"]))
    return _lin(y, ap["wo"], quant)


STRETCH = 128


def _delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token.  q, k ``(T, nv, dk)``, v ``(T, nv,
    dv)``, g, beta ``(T, nv)`` -> ``(T, nv, dv)``.  The state ``S (nv, dk,
    dv)`` starts at zero.  The scan runs in stretches of ``STRETCH`` steps,
    each under ``jax.checkpoint``: a backward pass keeps one state a stretch
    and computes a stretch's states again, never all ``T`` of them."""
    T, nv, dk = q.shape
    dv = v.shape[-1]

    def token(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[:, None, None]
        d = (vt - jnp.einsum("hkd,hk->hd", S, kt)) * bt[:, None]
        S = S + kt[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkd,hk->hd", S, qt)

    n = next(c for c in range(min(STRETCH, T), 0, -1) if T % c == 0)
    xs = tuple(a.reshape(T // n, n, *a.shape[1:]) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(jax.checkpoint(lambda S, x: jax.lax.scan(token, S, x)),
                        jnp.zeros((nv, dk, dv), jnp.float32), xs)
    return o.reshape(T, nv, dv)


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _gated_delta_net(h, gp, s, quant):
    T = h.shape[0]
    nk, nv, dk, dv, K = s["nk"], s["nv"], s["dk"], s["dv"], s["K"]
    n_qkv = 2 * nk * dk + nv * dv
    qkvz = _lin(h, gp["in_proj_qkvz"], quant)
    ba = _lin(h, gp["in_proj_ba"], quant)
    qkv, z = qkvz[:, :n_qkv], qkvz[:, n_qkv:]
    # torch conv1d, groups = channels, K - 1 zeros on the left, no bias
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j:j + T] * gp["conv_w"][:, j] for j in range(K)))
    q = qkv[:, :nk * dk].reshape(T, nk, dk)
    k = qkv[:, nk * dk:2 * nk * dk].reshape(T, nk, dk)
    v = qkv[:, 2 * nk * dk:].reshape(T, nv, dv)
    q = jnp.repeat(_l2norm(q) * dk ** -0.5, nv // nk, axis=1)   # value head h reads key head h // (nv / nk)
    k = jnp.repeat(_l2norm(k), nv // nk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(gp["A_log"]) * jax.nn.softplus(ba[:, nv:] + gp["dt_bias"])
    o = _delta_rule(q, k, v, g, beta)
    o = _rms(o, gp["norm"], s["eps"], centered=False) * jax.nn.silu(z.reshape(T, nv, dv))
    return _lin(o.reshape(T, nv * dv), gp["out_proj"], quant)


def _experts(h, _, mp, s, quant):
    """The expert layer on a block of rows: the router over all experts, the
    held experts one after another on every row behind the mask of their
    weights, the shared expert once."""
    C, I, E = s["C"], s["I"], s["E"]
    probs = jax.nn.softmax(_lin(h, mp["gate"], quant), axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, s["k"])
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # weight of each held expert on each row (0 where it was not picked)
    held = s["first"] + jnp.arange(E)
    w_held = jnp.sum(top_w[:, :, None] * (top_idx[:, :, None] == held[None, None, :]), axis=1)   # (t, E)

    def expert(y, a):
        w1, w3, w2, we = a
        out = _lin(jax.nn.silu(_lin(h, w1.T, quant)) * _lin(h, w3.T, quant), w2.T, quant)
        return y + we[:, None] * out, None

    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(h),
                        (mp["fc_1"].reshape(E, C, I), mp["fc_2"].reshape(E, C, I),
                         mp["proj"].reshape(E, I, C), w_held.T))
    sp = mp["shared"]
    shared = _lin(jax.nn.silu(_lin(h, sp["fc_1"], quant)) * _lin(h, sp["fc_2"], quant), sp["proj"], quant)
    return y + jax.nn.sigmoid(_lin(h, sp["gate"], quant)) * shared


def _block(x, bp, cos, sin, s, kind, quant):
    """One block on ``x (T, C)`` float32."""
    h = _rms(x, bp["norm_1"], s["eps"], centered=True)
    if kind == "linear_attention":
        x = x + _gated_delta_net(h, bp["gdn"], s, quant)
    else:
        x = x + _gated_attention(h, bp["attn"], cos, sin, s, quant)
    return x + _by_rows(functools.partial(_experts, s=s, quant=quant),
                        _rms(x, bp["norm_2"], s["eps"], centered=True), bp["mlp"])


def _f32(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("hf_items", "kind", "quant"))
def _ref_block(x, bp, cos, sin, *, hf_items, kind, quant):
    with jax.default_matmul_precision("highest"):
        return _block(x, _f32(bp), cos, sin, sizes(dict(hf_items)), kind, quant)


@functools.partial(jax.jit, static_argnames=("hf_items", "kind", "quant"))
def _ref_block_grads(x, bp, cos, sin, dy, *, hf_items, kind, quant):
    """The gradient of the loss in one block's input and in its weights
    (float32), from the gradient ``dy`` in its output."""
    s = sizes(dict(hf_items))
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(lambda x_, p_: _block(x_, p_, cos, sin, s, kind, quant), x, _f32(bp))
        return vjp(dy)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ref_logits(x, ln_f, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, ln_f.astype(jnp.float32), eps, centered=True) @ head.astype(jnp.float32).T


def _hf_items(hf: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in hf.items() if not isinstance(v, (dict, list))))


def ref_hidden(hf: dict, params: dict, tokens, *, quant: bool = False, keep: list | None = None):
    """Final hidden states ``(T, C)`` before the last norm, for one sequence
    of token ids ``(T,)``.  One block's weights are upcast at a time.  Every
    block's input is appended to ``keep``, where one is given."""
    items = _hf_items(hf)
    cos, sin = rope_tables(hf, tokens.shape[0])
    x = params["wte"][tokens].astype(jnp.float32)
    for bp, kind in zip(params["blocks"], layer_types(hf)):
        if keep is not None:
            keep.append(x)
        x = _ref_block(x, bp, cos, sin, hf_items=items, kind=kind, quant=quant)
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
        lg = _rms(x_, ln_f_, eps, centered=True) @ head_.T
        picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked)

    with jax.default_matmul_precision("highest"):
        total, grads = jax.value_and_grad(lambda *a: scale * ce(*a), argnums=(0, 1, 2))(
            x, ln_f.astype(jnp.float32), head.astype(jnp.float32))
        return total / scale, grads


@functools.partial(jax.jit, static_argnames=("rows",))
def _scatter_rows(dx, tokens, *, rows):
    return jax.ops.segment_sum(dx, tokens, num_segments=rows)


# A DeltaNet layer's ``A_log`` and ``dt_bias`` are one number a value head, and
# their gradient is ``sum_t dg_t * g_t`` (times a positive factor for
# ``dt_bias``): at initialisation the terms of a slowly decaying head cancel
# 250-fold (sum of magnitudes 1.2e-3, sum 4.8e-6 at the rehearsal size), and the
# heads with A over about 2 (most of ``U(0, 16)``) forget within a token and have
# a gradient of 1e-9.  The float32 recurrence fed the program's own bfloat16
# ``q, k, v, do`` gives the program's number; fed float32 activations it gives
# another by 10-40%, 4x where a block drew no slow head: the leaf measures the
# rounding of the activations, not the backward pass.  The same ``dg``, token
# by token and before the sum, is compared in ``in_proj_ba``'s gradient.
NOT_COMPARED = ("A_log", "dt_bias")


def ref_loss_and_grads(hf: dict, params: dict, idx, targets, *, quant: bool = False,
                       every_leaf: bool = False):
    """The mean next-token cross-entropy over a batch ``(B, T)``, and an
    iterator over its gradient in the weights, by a plain backward pass in
    float32.  The iterator gives one part of the weights' tree at a time, as
    ``(where, part)``: ``((), {"ln_f", "lm_head"})``, then ``(("blocks", i),
    block)`` from the last block to the first, then ``((), {"wte"})``; a
    DeltaNet block's ``NOT_COMPARED`` leaves are left out unless
    ``every_leaf``.  With ``quant`` the linear layers of the blocks (the
    experts' and the router's too) compute in float8, forward and backward
    (the control)."""
    items, eps = _hf_items(hf), hf["rms_norm_eps"]
    kinds = layer_types(hf)
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
                                             hf_items=items, kind=kinds[i], quant=quant)
                g_block = add(g_block, g)
            if "gdn" in g_block and not every_leaf:
                g_block["gdn"] = {k: v for k, v in g_block["gdn"].items() if k not in NOT_COMPARED}
            yield ("blocks", i), g_block
        g_wte = None
        for b in range(B):
            g_wte = add(g_wte, _scatter_rows(dys[b], idx[b], rows=params["wte"].shape[0]))
        yield (), {"wte": g_wte}

    return total / (B * T), grads()
