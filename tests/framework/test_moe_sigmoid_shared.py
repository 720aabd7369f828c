"""``distributed.moe``: the routing function as a parameter of the
dropless expert layer (sigmoid scores with a correction bias,
renormalised and scaled) and a shared expert beside the routed ones,
each against a loop over the experts."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed import moe

D, F, E, K = 64, 48, 8, 2


def _weights(rng):
    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.1, jnp.float32)
    return {"router": w(D, E), "wg": w(E, D, F), "wu": w(E, D, F),
            "wd": w(E, F, D), "bias": w(E), "sg": w(D, F), "su": w(D, F),
            "sd": w(F, D)}


def _swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def _loop(x, p, scaling=2.0, with_bias=True, with_shared=True):
    """Every token through its experts, one (token, expert) at a time."""
    x64 = np.asarray(x, np.float64)
    scores = 1 / (1 + np.exp(-(x64 @ np.asarray(p["router"], np.float64))))
    biased = scores + (np.asarray(p["bias"], np.float64) if with_bias
                       else 0.0)
    out = np.zeros_like(x64)
    chosen = []
    for t in range(x64.shape[0]):
        idx = np.argsort(-biased[t])[:K]
        chosen.append(sorted(idx.tolist()))
        w = scores[t, idx] / (scores[t, idx].sum() + 1e-20) * scaling
        for e, we in zip(idx, w):
            out[t] += we * np.asarray(_swiglu(
                x[t], p["wg"][e], p["wu"][e], p["wd"][e]), np.float64)
        if with_shared:
            out[t] += np.asarray(_swiglu(x[t], p["sg"], p["su"], p["sd"]),
                                 np.float64)
    return out, chosen


def _sigmoid(p, scaling=2.0):
    return lambda m, rw: moe.route_sigmoid_topk(m, rw, p["bias"], K, True,
                                                scaling)


@pytest.mark.parametrize("route", ["plain", "interpret"])
def test_sigmoid_routing_and_the_shared_expert_are_the_loops(route):
    rng = np.random.default_rng(0)
    p = _weights(rng)
    x = jnp.asarray(rng.normal(size=(29, D)), jnp.float32)
    want, chosen = _loop(x, p)
    y, counts, (weights, idx) = moe.dropless_moe(
        x, p["router"], p["wg"], p["wu"], p["wd"], top_k=K, route=route,
        router=_sigmoid(p), shared=(p["sg"], p["su"], p["sd"]))
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert [sorted(r) for r in np.asarray(idx).tolist()] == chosen
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.0, atol=1e-5)
    assert int(counts.sum()) == 29 * K
    # the bias picks and never weighs: without it the choice changes
    no_bias, other = _loop(x, p, with_bias=False)
    assert other != chosen and np.abs(no_bias - want).max() > 1e-3
    routed, _, _ = moe.dropless_moe(
        x, p["router"], p["wg"], p["wu"], p["wd"], top_k=K, route=route,
        router=_sigmoid(p))
    np.testing.assert_allclose(routed, _loop(x, p, with_shared=False)[0],
                               atol=2e-5)


@pytest.mark.parametrize("route", ["plain", "interpret"])
def test_two_halves_plus_the_shared_expert_once_add_up(route):
    rng = np.random.default_rng(1)
    p = _weights(rng)
    x = jnp.asarray(rng.normal(size=(21, D)), jnp.float32)
    want, _ = _loop(x, p)
    lo, lo_counts, _ = moe.dropless_moe(
        x, p["router"], p["wg"][:4], p["wu"][:4], p["wd"][:4], top_k=K,
        expert_lo=0, route=route, router=_sigmoid(p),
        shared=(p["sg"], p["su"], p["sd"]))
    hi, hi_counts, _ = moe.dropless_moe(
        x, p["router"], p["wg"][4:], p["wu"][4:], p["wd"][4:], top_k=K,
        expert_lo=4, route=route, router=_sigmoid(p))
    np.testing.assert_allclose(lo + hi, want, atol=2e-5)
    assert lo_counts.tolist() == hi_counts.tolist()  # the router sees all
    valid = jnp.arange(21) % 2 == 0
    part, some, _ = moe.dropless_moe(
        x, p["router"], p["wg"], p["wu"], p["wd"], top_k=K, route=route,
        valid=valid, router=_sigmoid(p))
    assert int(some.sum()) == int(valid.sum()) * K
    assert not np.asarray(part)[1::2].any()


def test_the_layer_creates_and_holds_what_its_options_say():
    paddle.seed(5)
    whole = moe.DroplessMoE(D, F, E, K, scoring="sigmoid",
                            routed_scaling_factor=2.0, shared_width=F)
    assert whole.e_score_correction_bias.shape == [E]
    assert whole.shared_gate_proj.shape == [D, F] and whole.holds_shared
    first = moe.DroplessMoE(D, F, E, K, scoring="sigmoid", shared_width=F,
                            expert_range=(0, 4))
    second = moe.DroplessMoE(D, F, E, K, scoring="sigmoid", shared_width=F,
                             expert_range=(4, 8))
    assert first.holds_shared and not second.holds_shared
    assert not hasattr(second, "shared_gate_proj")
    plain = moe.DroplessMoE(D, F, E, K)
    assert plain.e_score_correction_bias is None and not plain.holds_shared
    names = [n for n, _ in plain.named_parameters()]
    assert names == ["router", "gate_proj", "up_proj", "down_proj"]
    with pytest.raises(ValueError, match="scoring"):
        moe.DroplessMoE(D, F, E, K, scoring="tanh")
    x = paddle.to_tensor(np.random.default_rng(2).normal(
        size=(2, 5, D)).astype("float32"))
    counts, routed = [], []
    y = whole(x, counts_sink=counts, route_sink=routed)
    assert y.shape == [2, 5, D] and int(counts[0]._data.sum()) == 10 * K
    p = {"router": whole.router._data, "wg": whole.gate_proj._data,
         "wu": whole.up_proj._data, "wd": whole.down_proj._data,
         "bias": whole.e_score_correction_bias._data,
         "sg": whole.shared_gate_proj._data,
         "su": whole.shared_up_proj._data,
         "sd": whole.shared_down_proj._data}
    want, _ = _loop(x._data.reshape(10, D), p)
    np.testing.assert_allclose(y._data.reshape(10, D), want, atol=2e-5)
