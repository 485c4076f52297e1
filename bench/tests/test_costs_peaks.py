"""Operations and bytes per kernel call and per model token, against
values worked out by hand at qwen2-7b and deepseek-7b widths; the peak
table refuses a chip it does not know."""
import pytest

from bench import costs, peaks

QWEN = {"d": 3584, "h": 28, "kv": 4, "dh": 128, "ff": 18944,
        "vocab": 152064, "layers": 28}
DEEPSEEK = {"d": 4096, "h": 32, "kv": 32, "dh": 128, "ff": 11008,
            "vocab": 102400, "layers": 15}


def test_paged_decode_qwen2_two_slots():
    # 300 live entries over 4 kv heads, 7 query heads each, dh 128:
    # 4 * 300 * 4 * 7 * 128 FLOPs; K and V rows 2 * 300 * 4 * 128 * 2 B,
    # q in and out 2 * 2 slots * 28 heads * 128 * 2 B
    flops, nbytes = costs.paged_decode_call([100, 200], kv_heads=4, group=7,
                                            dh=128)
    assert flops == 4_300_800
    assert nbytes == 614_400 + 28_672


def test_paged_decode_deepseek_one_long_slot():
    flops, nbytes = costs.paged_decode_call([2048], kv_heads=32, group=1,
                                            dh=128)
    assert flops == 33_554_432
    assert nbytes == 33_554_432 + 16_384


def test_qkv_projection_qwen2_decode_batch():
    # fused width (28 + 2 * 4) * 128 = 4608; 16 rows of 3584
    flops, nbytes = costs.qkv_matmul_call(16, d_model=3584, heads=28,
                                          kv_heads=4, dh=128)
    assert flops == 2 * 16 * 3584 * 4608 == 528_482_304
    assert nbytes == 114_688 + 33_030_144 + 147_456


def test_qkv_projection_deepseek_chunk():
    # fused width (32 + 64) * 128 = 12288; one 32-token chunk of 4096
    flops, nbytes = costs.qkv_matmul_call(32, d_model=4096, heads=32,
                                          kv_heads=32, dh=128)
    assert flops == 3_221_225_472
    assert nbytes == 262_144 + 100_663_296 + 786_432


def test_model_flops_per_token():
    # qwen2-7b: 233,046,016 matmul weights a layer (QKV 16,515,072, O
    # 12,845,056, MLP 203,685,888); a decode token at context 1 also
    # does 4 * 28 * 128 attention FLOPs a layer and the LM head
    assert costs.layer_matmul_params(QWEN) == 233_046_016
    assert costs.token_flops(QWEN, 1, lm_head=True) == 14_140_973_056
    assert costs.token_flops(QWEN, 1, lm_head=False) == 13_050_978_304
    # deepseek-7b: QKV 50,331,648 + O 16,777,216 + MLP 135,266,304
    assert costs.layer_matmul_params(DEEPSEEK) == 202_375_168
    assert costs.token_flops(DEEPSEEK, 2048, lm_head=True) == (
        15 * (2 * 202_375_168 + 4 * 2048 * 32 * 128) + 2 * 4096 * 102400)


def test_prefill_flops_is_the_sum_of_its_tokens():
    # a chunk of positions 64..95 costs what its 32 tokens cost one by
    # one, each attending over its position + 1 entries, with no LM head
    one_by_one = sum(costs.token_flops(QWEN, p + 1, lm_head=False)
                     for p in range(64, 96))
    assert costs.prefill_flops(QWEN, 64, 32) == one_by_one


def test_roofline_takes_the_larger_bound():
    v5e = peaks.peaks("TPU v5 lite")
    assert costs.roofline_seconds(197e12, 0, v5e) == pytest.approx(1.0)
    assert costs.roofline_seconds(0, 819e9, v5e) == pytest.approx(1.0)
    assert costs.roofline_seconds(197e12, 2 * 819e9, v5e) == \
        pytest.approx(2.0)


def test_v5e_peaks_as_published():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["int8_ops"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peaks(kind)
