"""Operations and bytes against values worked by hand."""
from bench import counts, peaks


def test_least_time_is_the_slower_of_compute_and_bytes():
    p = peaks.peaks_for("TPU v5 lite")
    # a decode-sized int8 FFN call, 64 x 896 x 4864: bound by its bytes
    ops, nbytes = 2 * 64 * 896 * 4864, 57_344 + 4_358_144 + 311_296
    assert peaks.least_time_s(p, int8_ops=ops, bytes_moved=nbytes) \
        == nbytes / 819e9
    # bf16 and int8 work add, each at its own peak
    assert peaks.least_time_s(p, bf16_flops=197e12, int8_ops=393e12) == 2.0


def test_qwen2_token_at_published_widths():
    cfg = {"n_layers": 24, "d_model": 896, "n_heads": 14, "n_kv_heads": 2,
           "head_dim": 64, "d_ff": 4864, "vocab": 151936, "act": "silu"}
    one = counts.lm_token(cfg, 1, "bf16")
    # per layer: q,k,v,o 2*896*(896+128+128) + 2*896*896 = 3_670_016;
    # attention over one key 4*896 = 3_584; SwiGLU 3*2*896*4864 =
    # 26_148_864; the tied head 2*896*151936 = 272_269_312
    assert one["bf16_flops"] == 24 * (3_670_016 + 3_584 + 26_148_864) \
        + 272_269_312 == 988_008_448
    assert one["int8_ops"] == 0
    q8 = counts.lm_token(cfg, 1)
    assert q8["int8_ops"] == 24 * 26_148_864
    assert q8["bf16_flops"] + q8["int8_ops"] == one["bf16_flops"]


def test_lm_sequence_is_the_sum_of_its_tokens():
    cfg = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
           "head_dim": 4, "d_ff": 16, "vocab": 32, "act": "silu"}
    s = counts.lm_sequence(cfg, 3, 9)
    for k in ("bf16_flops", "int8_ops"):
        assert s[k] == sum(counts.lm_token(cfg, p + 1)[k] for p in range(3, 9))
    one = counts.lm_token(cfg, 5)
    assert one["int8_ops"] == 2 * 3 * 2 * 8 * 16
    proj = 2 * 8 * (8 + 2 * 4) + 2 * 8 * 8
    assert one["bf16_flops"] == 2 * (proj + 4 * 8 * 5) + 2 * 8 * 32


def test_unknown_chip_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v4")
