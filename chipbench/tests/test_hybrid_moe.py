"""The hybrid sparse decoder's own by-hand checks: parameters and operations
against counts made by hand for the published widths (hidden 2048; DeltaNet 16
key and 32 value heads of 128, conv 4; attention 16 heads of 256 and 2 KV
heads; 512 experts of width 512, ten a token, one shared), the scan kernel's
and the grouped products' work from shapes, the manifest's new entries, and
the cell's comparison told apart from its float8 control at the rehearsal
size."""
import argparse

from chipbench import calibrate, common
from chipbench.models import hybrid_moe_decoder as arch

CELL = "qwen3next-train-1chip.seq8k-x2"
CFG = common.load_json("configs", "qwen3next-train-1chip.json")
M = common.manifest()


def test_parameters_by_part_and_of_the_model():
    gdn = 2048 * (2048 + 2048 + 4096 + 4096) + 2048 * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * 2048
    attn = 2048 * 16 * 256 * 2 + 2 * 2048 * 2 * 256 + 16 * 256 * 2048 + 2 * 256
    outside = 512 * 2048 + 3 * 2048 * 512 + 2048 + 2 * 2048     # router, shared expert, its gate, two norms
    assert arch.mixer_params(CFG, "linear_attention") == gdn == 33_718_464
    assert arch.mixer_params(CFG, "full_attention") == attn == 27_263_488
    assert arch.expert_params(CFG) == 3 * 2048 * 512 == 3_145_728
    assert arch.layer_params(CFG, "linear_attention") == gdn + outside + 32 * 3_145_728 == 138_582_208
    assert arch.layer_params(CFG, "full_attention") == attn + outside + 32 * 3_145_728 == 132_127_232
    assert arch.param_count(CFG) == 3 * 138_582_208 + 132_127_232 + 2 * 18992 * 2048 + 2048 == 625_667_136
    # the 8-way share ISSUE 28 asked for first (64 experts held): what did not fit
    assert arch.param_count(dict(CFG, num_experts=64)) == 1_028_320_320
    assert arch.layer_types(CFG) == ("linear_attention",) * 3 + ("full_attention",)
    # the whole model, as published: 48 layers, every expert, the whole vocabulary
    whole = dict(CFG, num_hidden_layers=48, num_experts=512, vocab_size=151936)
    assert round(arch.param_count(whole) / 1e9, 1) == 79.7


def test_scan_operations_a_chunk_and_a_token():
    k = common.load_module("kernels", "gdn_chunk")
    C, dk, dv = 64, 128, 128
    products = (2 * C * C * dk           # K K^T, Q K^T
                + C * C * dv + C * C * dk   # the inverse applied to beta V and to beta e^G K
                + 3 * C * dk * dv           # against the state, the read-out from it, its update
                + C * C * dv                # (Q K^T) D
                + C ** 3 / 3)               # the inverse, as a forward substitution
    assert k.chunk_macs(C, dk, dv) == products
    assert arch.gdn_scan_macs_per_token(CFG) == 32 * products / 64
    w = k.call_work(CFG, 8192, 2)
    assert w["fwd_flops"] == 2 * (2 * 8192 / 64) * 32 * products
    assert w["fwd_bytes"] == 2 * 8192 * (2 * (2 * 16 * 128 + 2 * 32 * 128) + 8 * 32)
    peaks = common.peaks("TPU v5 lite")
    fwd = max(w["fwd_flops"] / 197e12, w["fwd_bytes"] / 819e9)
    assert k.least_seconds(CFG, 8192, 2, peaks, fwd_calls=3) == 3 * fwd
    # the backward kernel: two products for each of the forward's; the operands' gradients out
    assert w["bwd_flops"] == 2 * w["fwd_flops"]
    assert w["bwd_bytes"] == 2 * 8192 * (2 * (4 * 16 * 128 + 3 * 32 * 128) + 16 * 32)
    bwd = max(w["bwd_flops"] / 197e12, w["bwd_bytes"] / 819e9)
    assert k.least_seconds(CFG, 8192, 2, peaks, fwd_calls=6, bwd_calls=3) == 6 * fwd + 3 * bwd


def test_grouped_product_work_follows_the_expected_rows():
    k = common.load_module("kernels", "moe_grouped_mm")
    w = k.call_work(CFG, 8192, 2)
    rows = 2 * 8192 * 10 * 32 / 512
    assert rows == 10240 and w["flops"] == 2 * rows * 2048 * 512
    assert w["bytes"] == 2 * (rows * (2048 + 512) + 32 * 2048 * 512)
    # memory bound at these rows; nine products a layer a step, whatever the calls in the trace
    peaks = common.peaks("TPU v5 lite")
    assert k.least_seconds(CFG, 8192, 2, peaks, steps=2.5) == 2.5 * 4 * 9 * w["bytes"] / 819e9 > 2.5 * 4 * 9 * w["flops"] / 197e12


def test_train_flops_per_token_by_part():
    m = arch.forward_macs_per_token(CFG, 8192)
    assert m["gdn_mixers"] == 3 * (2048 * (12288 + 64) + 8192 * 4 + 4096 * 2048)
    assert m["attention"] == 2048 * 256 * (3 * 16 + 2 * 2) + 2 * 16 * 256 * 8193 / 2
    assert m["experts"] == 4 * (10 * 32 / 512) * 3_145_728           # 0.625 routed rows a token a layer
    assert m["router_shared"] == 4 * (512 * 2048 + 3 * 2048 * 512 + 2048)
    assert m["head"] == 18992 * 2048
    assert arch.train_flops_per_token(CFG, 8192) == 6 * sum(m.values())
    assert round(sum(m.values()) / 1e6) == 234


def test_the_manifest_gained_one_configuration_one_cell_and_their_metrics():
    (cell,) = [w for w in M["workloads"] if w["config"] == "qwen3next-train-1chip"]
    assert cell == {k: cell[k] for k in ("name", "config", "traffic", "chips", "why")}
    assert cell["name"] == CELL and cell["chips"] == 1 and len(cell["why"]) <= 200
    (entry,) = [c for c in M["configs"] if c["name"] == "qwen3next-train-1chip"]
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (CFG["published_num_hidden_layers"], CFG["published_num_experts"], CFG["published_vocab_size"]) == (
        48, 512, 151936)
    listed = {m["name"] for m in M["end_to_end"] + M["per_layer"] if CELL in m.get("workloads", [])}
    # since PR 50 a quantity one reader serves is one entry an end-to-end metric (`.train`, both train cells); the
    # six shares by scope keep their split until tests/test_op_scopes.py is rewritten
    by_scope = {f"{q}_share_of_busy.hyb" for q in ("mixer", "mlp", "head", "unscoped", "optimizer", "backward")}
    own = {"gdn_scan_roofline_share.hyb", "gdn_share_of_busy.hyb", "moe_grouped_mm_roofline_share.hyb",
           "causal_conv_roofline_share.hyb"}
    folded = {"moe_share_of_busy.train", "moe_glue_share_of_busy.train", "device_idle_share.train",
              "pallas_share_of_busy.train", "hbm_peak_share.train"}
    assert listed == {"train_tok_per_s_per_chip", "train_host_dispatch_ms", "train_step_device_ms", "train_mfu",
                      "flash_sdpa_roofline_share"} | by_scope | own | folded
    for m in M["per_layer"]:
        if m["name"] in listed and "." in m["name"]:                      # by_scope, own and folded
            assert m["workloads"][-1] == CELL and m["moves"] == "train_tok_per_s_per_chip"
            assert len(m["workloads"]) == 1 or m["name"].endswith(".train")
            assert callable(common.load_reader(m["name"]).read)


def test_every_published_width_is_the_catalogs():
    published = {"hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16, "num_key_value_heads": 2,
                 "intermediate_size": 5120, "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
                 "num_experts_per_tok": 10, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
                 "linear_value_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
                 "full_attention_interval": 4, "partial_rotary_factor": 0.25, "rope_theta": 10000000,
                 "rms_norm_eps": 1e-06, "max_position_embeddings": 262144, "decoder_sparse_step": 1,
                 "norm_topk_prob": True, "tie_word_embeddings": False, "use_sliding_window": False,
                 "rope_scaling": None, "mlp_only_layers": [], "hidden_act": "silu", "model_type": "qwen3_next"}
    assert {k: CFG[k] for k in published} == published
    assert (CFG["num_hidden_layers"], CFG["num_experts"], CFG["vocab_size"]) == (4, 32, 18992)
    assert CFG["vocab_size"] * 8 == CFG["published_vocab_size"] and CFG["num_experts"] * 16 == CFG["published_num_experts"]


def test_the_configured_precision_passes_and_the_float8_control_fails():
    """At the rehearsal size on the CPU; the readings at the cell's own size,
    on the chip, are in the configuration's `check_why`."""
    for seed in (11, 12, 2**31 + 13):
        ctx = calibrate.context(argparse.Namespace(workload=CELL, rehearse=True), seed)
        driver = common.load_module("drivers", ctx["config"]["driver"])
        st = driver.build(ctx)
        control = driver.check(ctx, st, control=True)
        sound = driver.check(ctx, st)
        # every weight of every block, the embedding and the head: 17 a DeltaNet block less its A_log and
        # dt_bias (`arch.NOT_COMPARED`: their dg is compared in in_proj_ba), 16 the attention block
        assert sound["leaves_compared"] == 3 * 15 + 16 + 3
        assert sound["ok"], (seed, sound)
        assert not control["ok"], (seed, control)
        for number in ("layer_grad_rel_err", "grad_rel_err"):
            assert sound[number] <= sound[number + "_limit"] < control[number], (seed, number, sound, control)


def test_a_read_out_scaled_wrongly_is_not_correct_and_the_loss_is_what_says_so(monkeypatch):
    """The fault `loss_err_limit` is there for: a read-out whose scale is off
    (a norm's `1 + w` or eps gone wrong before the head).  The targets are
    random tokens, so the first step's loss follows the logits' scale and
    nothing else: a scale off by `d` moves the gradients by `d`, inside the
    cell's own gradient limits (0.16, 0.45) for `d` = 0.1, and the loss by `d`
    times the logits' variance less the batch's mean target logit: about 0.08
    at the cell's size (logits of std 0.9; limit 0.0036), 0.002 at the
    rehearsal size (std 0.16, 256 tokens; limit 0.0006), where a batch whose
    targets drew logits 0.02 over the mean hides it (seed 2**31 + 21 does)."""
    from thunder_tpu.models import llama

    hidden = llama.gpt_hidden
    monkeypatch.setattr(llama, "gpt_hidden", lambda *a, **k: hidden(*a, **k) * 1.1)
    for seed in (2**31 + 22, 2**31 + 23):
        ctx = calibrate.context(argparse.Namespace(workload=CELL, rehearse=True), seed)
        driver = common.load_module("drivers", ctx["config"]["driver"])
        faulty = driver.check(ctx, driver.build(ctx))
        assert not faulty["ok"], faulty
        assert faulty["loss_err"] > 3 * faulty["loss_err_limit"], faulty
        assert faulty["layer_grad_rel_err"] <= faulty["layer_grad_rel_err_limit"], faulty
        assert faulty["grad_rel_err"] <= CFG["check"]["grad_rel_err_limit"], faulty
    # the cell's own loss limit is three times the largest sound reading there (0.00115), not the dense cell's 0.01
    assert CFG["check"]["loss_err_limit"] == 0.0036
