"""models/experts.py: the router the three expert families share. Its weights
s[sel] and their gradient come from a compare against the expert ids; here
they are held, bit for bit, to the indexed form written out below, and the
mechanism is pinned: no gather and no scatter in ``route`` or in its vjp."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from nanosandbox_tpu.config import load_config
from nanosandbox_tpu.models import experts, family_of


def route_by_gather(x, w_router, bias, k, *, norm, scale, eps):
    """The reference: ``experts.route`` as it stood before PR 36, the
    selected scores fetched index by index."""
    s = jax.nn.sigmoid(jnp.dot(x, w_router.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    _, sel = lax.top_k(s + lax.stop_gradient(bias.astype(jnp.float32)), k)
    w = jnp.take_along_axis(s, sel, axis=1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return sel.astype(jnp.int32), w * scale


def _family_case(config_file):
    """(E, k, norm, scale, eps) as the family's shipped config and module
    give them."""
    cfg = load_config([os.path.join(os.path.dirname(__file__), "..",
                                    "configs", config_file)])
    return (cfg.num_experts, cfg.num_experts_per_tok, cfg.route_norm,
            cfg.route_scale, family_of(cfg).ROUTE_EPS)


CASES = {
    "trinity-mini": lambda: _family_case("train_trinity_mini_ep8.py"),
    "lfm2-8b-a1b": lambda: _family_case("train_lfm2_8b_a1b_ep4.py"),
    "moonlight-16b-a3b": lambda: _family_case(
        "train_moonlight_16b_a3b_ep8.py"),
    "toy": lambda: (8, 2, True, 2.0, 1e-20),
    "toy-no-norm": lambda: (8, 3, False, 1.5, 0.0),
}


def _operands(E, k, N=96, d=48, seed=36):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (N, d), jnp.float32)
    w_router = jax.random.normal(ks[1], (d, E), jnp.float32) * 0.2
    bias = jax.random.normal(ks[2], (E,), jnp.float32) * 0.3   # NOT zero
    dw = jax.random.normal(ks[3], (N, k), jnp.float32)
    return x, w_router, bias, dw


def _forward_and_gradients(route, x, w_router, bias, dw, k, *,
                           compiled=False, **kw):
    """sel, w and the gradients of sum(w * dw) with respect to x and the
    router; ``compiled``: as one jitted program, as a train step runs it
    (XLA is then free to fuse and to merge reduces), else op by op."""
    def f(x, w_router, bias, dw):
        (sel, w), vjp = jax.vjp(lambda a, b: route(a, b, bias, k, **kw),
                                x, w_router)
        dx, dw_router = vjp((np.zeros(sel.shape, jax.dtypes.float0), dw))
        return sel, w, dx, dw_router
    return (jax.jit(f) if compiled else f)(x, w_router, bias, dw)


def _assert_the_same(got, want):
    for name, a, b in zip(("sel", "w", "dx", "dw_router"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.parametrize("compiled", [False, True],
                         ids=["op-by-op", "one-program"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_compare_form_is_the_gather_form(case, compiled):
    E, k, norm, scale, eps = CASES[case]()
    x, w_router, bias, dw = _operands(E, k)
    kw = dict(norm=norm, scale=scale, eps=eps)
    want, got = (_forward_and_gradients(route, x, w_router, bias, dw, k,
                                        compiled=compiled, **kw)
                 for route in (route_by_gather, experts.route))
    _assert_the_same(got, want)
    # the bias moved the selection (or the case would not tell s + bias's
    # values from s[sel]) and no gradient reaches it
    plain, _ = experts.route(x, w_router, jnp.zeros(E), k, **kw)
    assert (np.asarray(plain) != np.asarray(got[0])).any()
    d_bias = jax.grad(lambda b: jnp.sum(
        experts.route(x, w_router, b, k, **kw)[1] * dw))(bias)
    np.testing.assert_array_equal(np.asarray(d_bias), np.zeros(E, np.float32))


@pytest.mark.parametrize("compiled", [False, True],
                         ids=["op-by-op", "one-program"])
def test_a_tie_is_broken_as_top_k_breaks_it(compiled):
    """Two experts with the same score + bias in every row: ``lax.top_k``
    takes the lower id, and the weights and gradients follow that id."""
    E, k = 8, 2
    x, w_router, bias, dw = _operands(E, k)
    w_router = w_router.at[:, 5].set(w_router[:, 1])    # s[:, 5] == s[:, 1]
    bias = jnp.zeros(E).at[1].set(4.0).at[5].set(4.0)   # and both on top
    want, got = (_forward_and_gradients(route, x, w_router, bias, dw, k,
                                        compiled=compiled, norm=True,
                                        scale=2.0, eps=1e-20)
                 for route in (route_by_gather, experts.route))
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.tile(np.array([1, 5], np.int32), (96, 1)))
    _assert_the_same(got, want)


INDEXED = {"gather", "scatter", "scatter-add"}


def _primitives(jaxpr) -> set:
    """Every primitive's name in a jaxpr and in the jaxprs its equations
    hold (pjit, custom_vjp, cond, ...)."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("case", ["trinity-mini", "lfm2-8b-a1b",
                                  "moonlight-16b-a3b"])
def test_route_and_its_vjp_index_nothing(case):
    E, k, norm, scale, eps = CASES[case]()
    x, w_router, bias, dw = _operands(E, k)
    kw = dict(norm=norm, scale=scale, eps=eps)
    forward = jax.make_jaxpr(
        lambda a, b, c: experts.route(a, b, c, k, **kw))(x, w_router, bias)

    def with_vjp(route):
        return jax.make_jaxpr(lambda a, b, c, g: _forward_and_gradients(
            route, a, b, c, g, k, **kw))(x, w_router, bias, dw)

    for jaxpr in (forward, with_vjp(experts.route)):
        found = _primitives(jaxpr.jaxpr)
        assert "top_k" in found and "eq" in found, found
        assert not found & INDEXED, found & INDEXED
    # the reference, read the same way, does index: the reader is not blind
    assert {"gather", "scatter-add"} <= _primitives(
        with_vjp(route_by_gather).jaxpr)
