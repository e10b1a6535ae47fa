"""The operation counts kept with the benchmark agree with XLA's cost
analysis of the same work at small shapes on the CPU, and the byte counts
follow from the shapes."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import registry

from chipbench_tiny import FL_SIZES, SERVE_SIZES


def _flops(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return float(cost["flops"])


@pytest.fixture(scope="module")
def yi():
    return registry.config_module("yi-6b-int8")


@pytest.fixture(scope="module")
def tiny_yi():
    return dict(registry.config("yi-6b-int8"), **SERVE_SIZES)


def test_quant_matmul_ops_match_xla(yi, tiny_yi):
    rows = 24
    for (ops, byt, n), (k, nn, _) in zip(yi.quant_matmul_calls(tiny_yi, rows),
                                         yi.matmul_shapes(tiny_yi)):
        x = jnp.ones((rows, k))
        w = jnp.ones((k, nn))
        assert ops == _flops(jnp.dot, x, w)
        # int8 codes, a float32 scale, bf16 activations in and out
        assert byt == k * nn + 4 + 2 * rows * (k + nn)


def test_matmul_params_are_the_model_less_its_embedding(yi):
    from repro.configs import get_config

    cfg = registry.config("yi-6b-int8")
    c = get_config("yi-6b")
    assert yi.matmul_params(cfg) == c.param_count() - c.vocab_size * c.d_model


def test_causal_attention_ops_match_xla(yi, tiny_yi):
    B, S = 2, 64
    m = yi.dims(tiny_yi)
    q = jnp.ones((B, S, m["H"], m["hd"]))

    def full(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        return jnp.einsum("bhqk,bkhd->bqhd", s, v)

    per_layer_full = _flops(full, q, q, q)
    ops, byt = yi.flash_attention_cost(tiny_yi, B, S)
    # a causal kernel needs the lower triangle only: (S + 1) / (2 S) of it
    assert ops == pytest.approx(m["L"] * per_layer_full * (S + 1) / (2 * S))
    assert byt == m["L"] * B * S * m["hd"] * (2 * m["H"] + 2 * m["KV"]) * 2
    assert yi.attention_ops(tiny_yi, S) == m["L"] * 4 * m["H"] * m["hd"] * S


def test_decode_attention_reads_whole_pages(yi, tiny_yi):
    m = yi.dims(tiny_yi)
    ops, byt = yi.flash_decode_cost(tiny_yi, [1, 16, 17], page=16)
    assert ops == m["L"] * 4 * m["H"] * m["hd"] * (1 + 16 + 17)
    kv_pages = 1 + 1 + 2
    assert byt == m["L"] * (kv_pages * 2 * 16 * m["KV"] * m["hd"] * 4
                            + 3 * 2 * m["H"] * m["hd"] * 2)


def test_resnet_forward_ops_match_xla():
    rn = registry.config_module("resnet34-cifar10-fl")
    cfg = dict(registry.config("resnet34-cifar10-fl"), **{**FL_SIZES, "width": 16})
    params = rn.init_params(cfg, 0)
    x = jnp.ones((1, cfg["image_hw"], cfg["image_hw"], cfg["channels"]))
    xla = _flops(lambda p, x: rn.forward(cfg, p, x), params, x)
    ours = rn.forward_ops(cfg)
    # XLA also counts the normalisation and activations, which ours leaves
    # out; the convolutions are nearly all of it
    assert 0.9 * xla <= ours <= xla
    assert rn.train_ops_per_image(cfg) == 3 * ours


def test_resnet34_parameter_count():
    rn = registry.config_module("resnet34-cifar10-fl")
    cfg = registry.config("resnet34-cifar10-fl")
    shapes = jax.eval_shape(lambda: rn.init_params(cfg, 0))
    n = sum(l.size for l in jax.tree_util.tree_leaves(shapes))
    assert n == 21_280_330
