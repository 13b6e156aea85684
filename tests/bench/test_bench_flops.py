"""The benchmark's operation counts and peak table."""
import dataclasses
import math

import jax
import pytest

from bench import flops, harness


def _matmul_params(cfg) -> int:
    from repro.models.lm import init_params
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    return sum(math.prod(s.shape) for path, s in leaves
               if len(s.shape) >= 2 and path[0].key != "embed"
               and not (path[0].key == "scan" and len(s.shape) == 2))


@pytest.mark.parametrize("layers", [1, 8, 24])
def test_dense_lm_flops_count_the_program_matmul_weights(layers):
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("internlm2-1.8b"), n_layers=layers)
    n = flops.dense_lm_matmul_params(cfg.d_model, cfg.n_layers, cfg.n_heads,
                                     cfg.n_kv_heads, cfg.hd, cfg.d_ff, cfg.vocab_size)
    assert n == _matmul_params(cfg)
    per_token = flops.dense_lm_train_flops_per_token(
        cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff,
        cfg.vocab_size, 2048)
    assert per_token == 6 * n + 12 * layers * 16 * 128 * 2048


def test_the_benchmark_config_matches_the_count():
    c = harness.find_cell("train-internlm2-s2k").config
    per_token = flops.dense_lm_train_flops_per_token(
        c["hidden_size"], c["num_hidden_layers"], c["num_attention_heads"],
        c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
        c["vocab_size"], 2048)
    assert per_token == 6 * 692_846_592 + 402_653_184  # 4.56 GFLOP a token


def test_kernel_costs_at_the_shapes_run_on_the_chip():
    assert flops.knn_topk_cost(1024, 8192, 64, 5) == {
        "flops": 1_098_907_648.0, "bytes": 2_433_024.0}
    assert flops.kmeans_assign_cost(65536, 10, 4) == {
        "flops": 5_898_240.0, "bytes": 2_621_780.0}


def test_peaks_known_and_unknown_kinds():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v4", "cpu", "source"):
        with pytest.raises(KeyError):
            flops.peaks(kind)
