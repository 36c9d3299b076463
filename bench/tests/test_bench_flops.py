"""FLOP and byte counts and the peaks table, against hand-worked numbers."""
import dataclasses

import pytest

from bench import flops, model, peaks


@pytest.fixture(scope="module")
def qwen():
    return model.dense(model.load("qwen1.5-0.5b.m4"))


@pytest.fixture(scope="module")
def granite():
    return model.dense(model.load("granite-3-2b.l10.m4"))


def test_qwen_counts(qwen):
    # 24 x (1024*64*(16+16+16+16) + 3*1024*2816)
    assert flops.layer_matmul_params(qwen) == 308_281_344
    assert flops.head_params(qwen) == 1024 * 151936 == 155_582_464
    # 2 bytes x (matmuls + head + 49 norms of 1024 + 24 x 3072 qkv biases)
    assert flops.weight_bytes_per_instance(qwen) == 927_975_424
    assert flops.kv_row_bytes(qwen) == 2 * 24 * 2 * 16 * 64 == 98_304
    assert flops.attn_flops(qwen, 1) == 4 * 24 * 16 * 64


def test_qwen_decode_step_is_memory_bound(qwen):
    # 32 live lanes on 4 instances, 300 positions each; the head is the
    # tied embedding, read whole, so no embedding rows are counted apart
    f, b = flops.decode_step(qwen, instances=4, lanes=32, ctx_sum=9600)
    assert f == 32 * 2 * 463_863_808 + 9600 * 98_304 == 30_631_002_112
    assert b == (4 * 927_975_424 + (9600 - 32) * 98_304
                 + 32 * 98_304) == 4_655_620_096
    t, bound = flops.least_seconds(f, b, peaks.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(4_655_620_096 / 819e9)


def test_untied_embedding_rows_are_read_apart(qwen):
    untied = dataclasses.replace(qwen, tied=False)
    _, tied_b = flops.decode_step(qwen, instances=4, lanes=32, ctx_sum=9600)
    _, b = flops.decode_step(untied, instances=4, lanes=32, ctx_sum=9600)
    # the separate head is counted in both; the untied table adds the
    # 32 rows looked up
    assert b - tied_b == 32 * 2 * 1024


def test_granite_counts(granite):
    # 10 x (2048*64*(32+32+8+8) + 3*2048*8192)
    assert flops.layer_matmul_params(granite) == 608_174_080
    assert flops.head_params(granite) == 2048 * 49155 == 100_669_440
    # no biases; 21 norms of 2048
    assert flops.weight_bytes_per_instance(granite) == \
        2 * (608_174_080 + 100_669_440 + 21 * 2048) == 1_417_773_056
    assert flops.kv_row_bytes(granite) == 2 * 10 * 2 * 8 * 64 == 20_480
    assert flops.attn_flops(granite, 1) == 4 * 10 * 32 * 64 == 81_920
    f, b = flops.decode_step(granite, instances=1, lanes=1, ctx_sum=1)
    assert f == 2 * 708_843_520 + 81_920
    assert b == 1_417_773_056 + 20_480


def test_compute_bound_when_flops_dominate():
    p = peaks.Peaks(flops_bf16=1e12, hbm_bytes=1e12)
    assert flops.least_seconds(3e12, 1e12, p) == (3.0, "compute")


def test_peaks_keyed_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bytes) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name, layer, head, attn1", [
    ("qwen1.5-0.5b.m4", 308_281_344, 155_582_464, 4 * 24 * 16 * 64),
    ("granite-3-2b.l10.m4", 608_174_080, 100_669_440, 4 * 10 * 32 * 64)])
def test_mfu_flops_per_token(name, layer, head, attn1):
    from bench import layers

    ctx = layers.LayerContext(
        model=model.dense(model.load(name)), slots=8,
        peaks=peaks.peaks("TPU v5 lite"), chips=1, window_s=1.0, busy_s=1.0,
        program_s={}, counters={}, queue_waits_s=[],
        # one token decoded at position 99 (attends over 100 positions);
        # a 32-token chunk prefilled at positions 0..31 (1 + ... + 32 = 528)
        decode_steps=[(1, 1, 100)], prefill=[(32, 528)])
    assert ctx.decode_flops() == 2 * (layer + head) + 100 * attn1
    # a prefilled token needs no logits
    assert ctx.prefill_flops() == 32 * 2 * layer + 528 * attn1
