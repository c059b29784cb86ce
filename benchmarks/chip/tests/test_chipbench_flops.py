"""Kernel FLOP and byte counts at the cells' shapes, and the MFU
arithmetic, against values worked out by hand.

The calls are the HLO texts the chip's trace shows for the cells' Pallas
kernels (shortened to what the parser reads).  Runs on the CPU."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip import trace as T  # noqa: E402

FLASH_PRIVATE = (
    "%closed_call.24 = (bf16[2,4,32,1024,128]{4,3,2,1,0:T(8,128)(2,1)}, "
    "f32[2,4,32,1024,1]{4,3,2,1,0:T(8,128)}) custom-call(bf16[2,4,32,1024,128]"
    "{4,3,2,1,0:T(8,128)(2,1)} %maximum_bitcast_fusion.20, bf16[2,4,8,1024,"
    "128]{4,3,2,1,0:T(8,128)(2,1)S(1)} %copy-done.35, bf16[2,4,8,1024,128]"
    "{4,3,2,1,0:T(8,128)(2,1)S(1)} %custom-call.61), custom_call_target="
    "\"tpu_custom_call\", operand_layout_constraints={bf16[2,4,32,1024,128]"
    "{4,3,2,1,0}, bf16[2,4,8,1024,128]{4,3,2,1,0}, bf16[2,4,8,1024,128]"
    "{4,3,2,1,0}}, frontend_attributes={kernel_metadata={}}")
FLASH_PUBLIC = FLASH_PRIVATE.replace("[2,4,", "[2,2,")
KL_DENSE = (
    "%jvp__.1 = f32[2,2048]{1,0:T(2,128)S(1)} custom-call(bf16[2,2048,20480]"
    "{2,1,0:T(8,128)(2,1)} %pad.13, bf16[2,2048,20480]{2,1,0:T(8,128)(2,1)} "
    "%pad.13, f32[2,2]{1,0:T(2,128)S(1)} %copy-done.166), custom_call_target"
    "=\"tpu_custom_call\", operand_layout_constraints={bf16[2,2048,20480]"
    "{2,1,0}, bf16[2,2048,20480]{2,1,0}, f32[2,2]{1,0}}")
KL_SPARSE = (
    "%jvp__.1 = f32[32,2,64]{2,1,0:T(2,128)S(1)} custom-call(bf16[2,2048,"
    "19200]{2,1,0:T(8,128)(2,1)} %pad.12, s32[2,2048,64]{2,1,0:T(8,128)S(1)}"
    " %copy-done.44, f32[2,2048,64]{2,1,0:T(8,128)S(1)} %get-tuple-element."
    "4075, f32[2,2]{1,0:T(2,128)S(1)} %copy-done.165), custom_call_target="
    "\"tpu_custom_call\"")
SSD = (
    "%closed_call.24 = (bf16[2,4,48,4,256,64]{5,4,3,2,1,0}, f32[2,4,48,64,"
    "128]{4,3,2,1,0}, f32[2,4,48,4,64,128]{5,4,3,2,1,0}) custom-call("
    "bf16[2,4,48,4,256,64]{5,4,3,2,1,0} %bitcast.1364, f32[2,4,48,4,256,1]"
    "{5,4,3,2,1,0} %copy.1138, f32[2,4,48,4,1,256]{5,4,3,2,1,0} "
    "%broadcast_in_dim.610, f32[2,48,1]{2,1,0} %copy-done.94, bf16[2,4,1,4,"
    "256,128]{5,4,3,2,1,0} %bitcast.1399, bf16[2,4,1,4,256,128]{5,4,3,2,1,0}"
    " %bitcast.1400), custom_call_target=\"tpu_custom_call\"")
FUSION = ("%fusion.1 = bf16[8192,2560]{1,0:T(8,128)(2,1)} fusion(bf16[2,"
          "18992,2560]{2,1,0} %p.1, s32[8192]{0} %bitcast.1160), kind=kCustom")

KERNELS = {"flash_attn_fwd_roofline": [FLASH_PRIVATE, FLASH_PUBLIC],
           "kl_mutual_pair_roofline": [KL_DENSE],
           "sparse_kl_topk_roofline": [KL_SPARSE],
           "ssd_scan_fwd_roofline": [SSD]}


def ctx_for(config: str):
    body = json.loads((ROOT / "benchmarks/chip/configs" /
                       f"{config}.json").read_text())
    cell = SimpleNamespace(config=body,
                           family=harness.family(body["reference"]))
    return SimpleNamespace(cell=cell)


def test_parse_hlo_reads_results_operands_and_target():
    call = T.parse_hlo(FLASH_PRIVATE)
    assert call.op == "closed_call.24"
    assert call.target == "tpu_custom_call"
    assert [(a.dtype, a.shape) for a in call.results] == [
        ("bf16", (2, 4, 32, 1024, 128)), ("f32", (2, 4, 32, 1024, 1))]
    assert [a.shape for a in call.operands] == [
        (2, 4, 32, 1024, 128), (2, 4, 8, 1024, 128), (2, 4, 8, 1024, 128)]
    fusion = T.parse_hlo(FUSION)
    assert fusion.target is None and len(fusion.operands) == 2
    assert T.parse_hlo("not an instruction") is None


@pytest.mark.parametrize("metric", sorted(KERNELS))
def test_each_kernel_matches_only_its_own_calls(metric):
    mod = harness.metric_module(metric)
    for name, texts in KERNELS.items():
        for text in texts:
            assert mod.match(T.parse_hlo(text)) == (name == metric), \
                (metric, text[:40])
    assert not mod.match(T.parse_hlo(FUSION))


def test_flash_attention_counts():
    mod = harness.metric_module("flash_attn_fwd_roofline")
    priv, pub = T.parse_hlo(FLASH_PRIVATE), T.parse_hlo(FLASH_PUBLIC)
    # 4 x (K B = 8) x 32 heads x 1024 * 1025 / 2 causal pairs x 128
    assert mod.flops(priv) == 68_786_585_600
    assert mod.flops(pub) == 34_393_292_800
    # q and out 67,108,864 each; k, v 16,777,216 each; lse 1,048,576
    assert mod.nbytes(priv) == 168_820_736
    assert mod.nbytes(pub) == 84_410_368


def test_flash_attention_counts_the_qwen3_family_as_full_layers():
    # the qwen3 family has no ``attention_pairs``: a cell's context counts
    # what the call's shapes alone count
    mod = harness.metric_module("flash_attn_fwd_roofline")
    ctx = ctx_for("qwen3-4b.2L.v1of8")
    assert not hasattr(ctx.cell.family, "attention_pairs")
    assert mod.flops(T.parse_hlo(FLASH_PRIVATE), ctx) == 68_786_585_600
    assert mod.flops(T.parse_hlo(FLASH_PUBLIC), ctx) == 34_393_292_800


def _window_pairs(S: int, window: int) -> int:
    """Causal pairs of one sequence when each query sees at most the last
    ``window`` keys, itself included, counted query by query."""
    return sum(min(i + 1, window) for i in range(S))


def test_flash_attention_counts_a_windowed_familys_pairs():
    # a family of a period S,S,S,F: a 1024 window on three of four layers
    assert _window_pairs(8192, 1024) == 7_864_832
    assert _window_pairs(8192, 8192) == 33_558_528

    def attention_pairs(config, S, T):
        kinds = config["layer_types"]
        return sum(_window_pairs(S, config["sliding_window"])
                   if kind == "sliding_attention" else S * (S + 1) // 2
                   for kind in kinds) / len(kinds)

    config = {"sliding_window": 1024,
              "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}
    family = SimpleNamespace(attention_pairs=attention_pairs)
    ctx = SimpleNamespace(cell=SimpleNamespace(config=config, family=family))
    call = T.parse_hlo(FLASH_PRIVATE.replace(",1024,", ",8192,"))
    assert call.operands[0].shape == (2, 4, 32, 8192, 128)
    mod = harness.metric_module("flash_attn_fwd_roofline")
    mean_pairs = (3 * 7_864_832 + 33_558_528) / 4
    assert mod.flops(call, ctx) == 4.0 * 8 * 32 * mean_pairs * 128
    # a full count would be 2.35 times the period's work
    assert mod.flops(call) / mod.flops(call, ctx) == pytest.approx(
        4 * 33_558_528 / (3 * 7_864_832 + 33_558_528))


def test_dense_kl_counts_at_the_true_vocabulary():
    mod = harness.metric_module("kl_mutual_pair_roofline")
    call = T.parse_hlo(KL_DENSE)
    ctx = ctx_for("qwen3-4b.2L.v1of8")                  # V = 18,992
    # 2048 positions x 18,992 x (4 (2 + 2) + 3 x 2 x 2)
    assert mod.flops(call, ctx) == 1_089_077_248
    # live and fixed 2 x 2048 x 18,992 x 2 B each, weights 16, out 16,384
    assert mod.nbytes(call, ctx) == 311_181_328


def test_sparse_kl_counts_at_the_true_vocabulary():
    mod = harness.metric_module("sparse_kl_topk_roofline")
    call = T.parse_hlo(KL_SPARSE)
    ctx = ctx_for("qwen3-4b.2L.v1of8")                  # V = 18,992
    # 4 x (Kl 2) x 2048 positions x 18,992 + 4 x 2 x (J 2) x 2048 x 64
    assert mod.flops(call, ctx) == 313_262_080
    # live 2 x 2048 x 18,992 x 2 B; indices and log-probabilities
    # 2 x 2048 x 64 x 4 B each; weights 16; the result 32 x 2 x 64 x 4 B
    assert mod.nbytes(call, ctx) == 157_696_016
    # without a configuration, at the vocabulary the call streams
    assert mod.nbytes(call) == 2 * 2048 * 19200 * 2 + 2 * 1_048_576 + 16 + \
        16_384


def test_ssd_counts():
    mod = harness.metric_module("ssd_scan_fwd_roofline")
    call = T.parse_hlo(SSD)
    # 1536 (batch, head, chunk) tiles x (2 L^2 N + 2 L^2 P + 4 L P N)
    assert mod.flops(call) == 1536 * 33_554_432
    # x, y 50,331,648 each (bf16); dt twice 1,572,864 each; A 384; B, C
    # 2,097,152 each; final state 12,582,912; entry states 50,331,648
    assert mod.nbytes(call) == 170_918_272


def test_least_time_takes_the_larger_bound():
    from benchmarks.chip import roofline as R
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert R.least_time(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert R.least_time(1.0, 819e9, peaks) == pytest.approx(1.0)


def test_mfu_arithmetic():
    mfu = harness.metric_module("mfu")
    ctx = ctx_for("qwen3-4b.2L.v1of8")
    fam, cfg = ctx.cell.family, ctx.cell.config
    # 6 x 250,470,400 matmul parameters + 3 x 2 layers x 4 x 32 x 128 x
    # 1025 / 2 attention FLOPs per token
    assert fam.matmul_params(cfg) == 250_470_400
    assert mfu.flops_per_token(fam, cfg, 1024) == 1_553_203_200
    cell = SimpleNamespace(config=cfg, family=fam, chips=1,
                           traffic={"seq": 1024}, tokens_per_round=12_288)
    run = SimpleNamespace(cell=cell, devices=[object()], rounds=3,
                          window_s=1.0, peaks={"bf16_flops": 197e12})
    # 3 rounds x 12,288 tokens x 1,553,203,200 over 1 s x 197 TFLOP/s
    assert mfu.read(run) == pytest.approx(
        100 * 3 * 12_288 * 1_553_203_200 / 197e12)
    mamba = ctx_for("mamba2-780m.8L")
    assert mamba.cell.family.matmul_params(mamba.cell.config) == 194_224_128
