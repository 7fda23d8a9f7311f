"""Operations and bytes computed from shapes, and the table of peaks."""
import pytest

from bench import common, work

QWEN2 = common.load_json(common.ROOT / "bench" / "configs" / "qwen2-1.5b.json")


def test_qwen2_parameter_count():
    # Qwen2-1.5B: 1.54 B parameters with the tied embedding counted once
    assert work.decoder_params(QWEN2) == pytest.approx(1.544e9, rel=1e-3)


def test_decode_step_work_from_the_live_batch():
    f1, b1 = work.decode_step_work(QWEN2, [1000], "bfloat16")
    f2, b2 = work.decode_step_work(QWEN2, [1000, 3000], "bfloat16")
    kv = work.kv_bytes_per_position(QWEN2, "bfloat16")
    assert kv == 2 * 28 * 2 * 128 * 2
    # the weights are read once however many requests there are; each
    # request adds its own live K/V, its new K/V and its logits row
    assert b2 - b1 == pytest.approx(3000 * kv + kv + 151936 * 2)
    assert b1 > 2 * work.decoder_params(QWEN2)
    # two FLOPs per parameter per token (the tied table counted once, as
    # the head: the embedding lookup is no multiply), plus attention
    assert f2 - f1 == pytest.approx(
        2 * work.decoder_params(QWEN2) + 4 * 3000 * 12 * 128 * 28)


def test_decode_is_bound_by_bandwidth():
    peak = common.peaks("TPU v5 lite")
    f, b = work.decode_step_work(QWEN2, [2000] * 32, "bfloat16")
    assert b / peak["hbm_bytes_per_s"] > f / peak["bf16_flops_per_s"]
    assert work.least_seconds(f, b, peak) == b / peak["hbm_bytes_per_s"]


def test_spmv_and_mlp_work():
    # StocF-1465-sized CSR: 186 MB at f32 with int32 indices
    b = work.spmv_csr_bytes(1_465_137, 1_465_137, 21_013_830)
    assert b == pytest.approx(4 * (1_465_138 + 2 * 21_013_830
                                   + 2 * 1_465_137))
    assert work.spmv_csr_flops(10) == 20
    # qwen2-1.5b's gated MLP block over 16384 tokens: three products
    f, b = work.swiglu_mlp_work(16384, 1536, 8960, "bfloat16")
    assert f == 2 * 16384 * 1536 * 8960 * 3
    assert b == 2 * (16384 * 1536 * 2 + 1536 * 8960 * 3)
    peak = common.peaks("TPU v5 lite")
    assert work.least_seconds(f, b, peak) == f / peak["bf16_flops_per_s"]


def test_peaks_table():
    p = common.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        common.peaks("TPU v9 imaginary")
