"""Operations and bytes from shapes, against a count made by hand for one
yi-6b layer (d 4096, 32 query and 4 KV heads of 128, d_ff 11008)."""

from __future__ import annotations

import pytest

from bench import core, work

YI = core.load_json(core.BENCH_DIR / "configs" / "yi-6b-int8-serve.json")
SMOL = core.load_json(core.BENCH_DIR / "configs" / "smollm-135m-a2q-train.json")

# one layer's weights: wq 4096x4096, wk/wv 4096x512, wo 4096x4096, three of 4096x11008
LAYER_W = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
LAYER_OUT = 4096 + 512 + 512 + 4096 + 11008 + 11008 + 4096   # output channels
LAYER_IN = 4096 * 6 + 11008                                   # input features over the 7 calls


@pytest.mark.parametrize("rows", [1, 24])
def test_one_layer_matmuls(rows):
    cfg = dict(YI, num_hidden_layers=1, tie_word_embeddings=True)
    got = work.decode_tick(cfg, rows, 0)["int_matmul"]
    assert got[0] == 2.0 * rows * LAYER_W
    # int8 weights + float32 inputs + bfloat16 outputs + a float32 scale per channel
    assert got[1] == LAYER_W + rows * LAYER_IN * 4 + rows * LAYER_OUT * 2 + 4 * LAYER_OUT


def test_head_counts_once():
    one = work.decode_tick(dict(YI, num_hidden_layers=1), 1, 0)["int_matmul"]
    tied = work.decode_tick(dict(YI, num_hidden_layers=1, tie_word_embeddings=True), 1, 0)["int_matmul"]
    assert one[0] - tied[0] == 2.0 * 4096 * 64000
    assert one[1] - tied[1] == 4096 * 64000 + 4096 * 4 + 64000 * 2 + 4 * 64000


@pytest.mark.parametrize("ctx", [1, 1000, 50000])
def test_paged_attention_one_layer(ctx):
    ops, nbytes = work.paged_attention(ctx, 24, 32, 4, 128)
    assert ops == 4.0 * 32 * 128 * ctx
    assert nbytes == ctx * 4 * (2 * 128 + 8) + 24 * 32 * 128 * 4


def test_whole_model_is_the_sum():
    t = work.decode_tick(YI, 24, 30000)
    assert t["model"][0] == t["int_matmul"][0] + t["paged_attention"][0]
    assert t["model"][1] == t["int_matmul"][1] + t["paged_attention"][1]
    # 32 layers of 173.0 M int8 codes and the 262.1 M of the head, read every tick
    assert 5.8e9 < t["int_matmul"][1] < 6.0e9


def test_prefill_chunk_attention():
    c = work.prefill_chunk(dict(YI, num_hidden_layers=1, tie_word_embeddings=True), 4, 10)
    mm_ops = 2.0 * 4 * LAYER_W
    keys = 4 * 10 + 4 * 5 / 2
    assert c["model"][0] == mm_ops + 4.0 * 32 * 128 * keys


def test_train_flops():
    per_layer = 576 * 576 * 2 + 576 * 192 * 2 + 576 * 1536 * 3
    n = 30 * per_layer + 576 * 49152
    attn = 6.0 * 2 * 30 * 576 * 2048 / 2
    assert work.train_flops_per_token(SMOL, 2048) == 6.0 * n + attn


@pytest.mark.parametrize("ops,nbytes,want", [
    (393e12, 819e9 / 2, (1.0, "compute")),
    (393e12 / 4, 819e9, (1.0, "memory")),
])
def test_least_seconds(ops, nbytes, want):
    assert work.least_seconds(ops, nbytes, 393e12, 819e9) == want
