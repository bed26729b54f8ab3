"""Numeric-vs-analytic gradient checking — the correctness backbone.

Reference: gradientcheck/GradientCheckUtil.java:62 (MLN), :194 (CG), :305 (pretrain) —
central finite-difference comparison used by the whole reference test suite
(SURVEY.md §4). Same contract here: perturb each parameter by +/-eps in float64,
compare (f(p+eps)-f(p-eps))/(2 eps) against the autodiff gradient, fail if max
relative error exceeds ``max_rel_error`` (absolute-error escape hatch for tiny grads).

Runs on CPU in float64 via jax.experimental.enable_x64 for numerical headroom —
float32 finite differences are too noisy for 1e-6-level checks.
"""
from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.utils.pytree import flatten_params, unflatten_params

log = logging.getLogger(__name__)


def check_gradients(net, x, y, *, eps: float = 1e-6, max_rel_error: float = 1e-3,
                    min_abs_error: float = 1e-8, subset: Optional[int] = None,
                    seed: int = 0, verbose: bool = False) -> bool:
    """Gradient-check a MultiLayerNetwork (or any object exposing
    gradient_and_score + params_list). Checks ``subset`` randomly-chosen parameters
    (all if None).
    """
    from deeplearning4j_tpu import common

    saved_policy = common.get_policy()
    common.set_policy(jnp.float64, jnp.float64, jnp.float64)
    try:
        return _check_gradients_x64(net, x, y, eps=eps, max_rel_error=max_rel_error,
                                    min_abs_error=min_abs_error, subset=subset,
                                    seed=seed, verbose=verbose)
    finally:
        common._POLICY = saved_policy


def check_pretrain_gradients(net, layer_idx: int, x, *, eps: float = 1e-6,
                             max_rel_error: float = 1e-3,
                             min_abs_error: float = 1e-8,
                             subset: Optional[int] = None, seed: int = 0,
                             rng_seed: int = 5, verbose: bool = False) -> bool:
    """Gradient-check one pretrain layer's unsupervised objective (reference
    GradientCheckUtil.checkGradientsPretrainLayer:305): forward the input to
    the layer, then finite-difference ``pretrain_loss`` wrt THAT layer's
    params against autodiff, with the sampling rng held fixed so the
    objective is a deterministic function of the parameters."""
    from deeplearning4j_tpu import common

    saved_policy = common.get_policy()
    common.set_policy(jnp.float64, jnp.float64, jnp.float64)
    try:
        with jax.enable_x64(True):
            layer = net.conf.layers[layer_idx]
            params64 = jax.tree_util.tree_map(
                lambda a: jnp.asarray(np.asarray(a), jnp.float64),
                net.params_list)
            h = jnp.asarray(np.asarray(x), jnp.float64)
            for i in range(layer_idx):
                pp = net.conf.preprocessor(i)
                if pp is not None:
                    h = pp.pre_process(h)
                h, _ = net.conf.layers[i].apply(
                    params64[i], net.state_list[i], h, train=False, rng=None)
            pp = net.conf.preprocessor(layer_idx)
            if pp is not None:
                h = pp.pre_process(h)
            key = jax.random.PRNGKey(rng_seed)

            def score(p_layer):
                return layer.pretrain_loss(p_layer, h, rng=key)

            return _fd_check_subtree(score, params64[layer_idx], eps=eps,
                                     max_rel_error=max_rel_error,
                                     min_abs_error=min_abs_error,
                                     subset=subset, seed=seed, verbose=verbose,
                                     tag="pretrain")
    finally:
        common._POLICY = saved_policy


def check_graph_pretrain_gradients(net, vertex_name: str, xs, *,
                                   eps: float = 1e-6,
                                   max_rel_error: float = 1e-3,
                                   min_abs_error: float = 1e-8,
                                   subset: Optional[int] = None, seed: int = 0,
                                   rng_seed: int = 5,
                                   verbose: bool = False) -> bool:
    """ComputationGraph twin of check_pretrain_gradients (reference
    GradientCheckUtil.checkGradientsPretrainLayer:305 applied to graph
    vertices): evaluate the vertex's ancestors in f64 eval mode, then
    finite-difference its pretrain objective wrt that vertex's params."""
    from deeplearning4j_tpu import common
    from deeplearning4j_tpu.nn.graph_network import eval_forward_to_vertex

    saved_policy = common.get_policy()
    common.set_policy(jnp.float64, jnp.float64, jnp.float64)
    try:
        with jax.enable_x64(True):
            conf = net.conf
            layer = conf.vertices[vertex_name].layer
            params64 = jax.tree_util.tree_map(
                lambda a: jnp.asarray(np.asarray(a), jnp.float64),
                net.params_list)
            inputs64 = [jnp.asarray(np.asarray(x), jnp.float64) for x in xs]
            h = eval_forward_to_vertex(conf, params64, net.state_list,
                                       inputs64, vertex_name)
            key = jax.random.PRNGKey(rng_seed)

            def score(p_vertex):
                return layer.pretrain_loss(p_vertex, h, rng=key)

            return _fd_check_subtree(score, params64[vertex_name], eps=eps,
                                     max_rel_error=max_rel_error,
                                     min_abs_error=min_abs_error,
                                     subset=subset, seed=seed, verbose=verbose,
                                     tag=f"graph pretrain[{vertex_name}]")
    finally:
        common._POLICY = saved_policy


def _fd_check_subtree(score, params_subtree, *, eps, max_rel_error,
                      min_abs_error, subset, seed, verbose, tag) -> bool:
    """Central finite-difference vs autodiff over one params subtree (the
    shared core of the MLN and CG pretrain checkers)."""
    analytic = jax.grad(score)(params_subtree)
    flat_analytic = np.asarray(flatten_params(analytic), np.float64)
    flat_params = np.asarray(flatten_params(params_subtree), np.float64)
    n = len(flat_params)
    if subset is not None and subset < n:
        indices = np.random.default_rng(seed).choice(n, subset, replace=False)
    else:
        indices = np.arange(n)
    score_jit = jax.jit(lambda flat: score(  # lint: adhoc-jit-ok (float64 finite-difference probe outside every dtype policy; never serves, never warm-starts)
        unflatten_params(params_subtree, flat)))
    fails = 0
    max_err = 0.0
    for i in indices:
        plus = flat_params.copy()
        plus[i] += eps
        minus = flat_params.copy()
        minus[i] -= eps
        numeric = (float(score_jit(jnp.asarray(plus)))
                   - float(score_jit(jnp.asarray(minus)))) / (2 * eps)
        a = flat_analytic[i]
        denom = max(abs(numeric), abs(a))
        rel = abs(numeric - a) / denom if denom > 0 else 0.0
        if rel > max_rel_error and abs(numeric - a) > min_abs_error:
            fails += 1
            if verbose:
                log.info("param %d: analytic=%.8g numeric=%.8g rel=%.3g",
                         i, a, numeric, rel)
        max_err = max(max_err,
                      rel if abs(numeric - a) > min_abs_error else 0.0)
    if verbose:
        log.info("%s gradient check: %d params, max rel err %.3g, "
                 "%d failures", tag, len(indices), max_err, fails)
    return fails == 0


def _check_gradients_x64(net, x, y, *, eps, max_rel_error, min_abs_error, subset,
                         seed, verbose) -> bool:
    with jax.enable_x64(True):
        params64 = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a), jnp.float64), net.params_list)
        x64 = jnp.asarray(np.asarray(x), jnp.float64)
        y64 = jnp.asarray(np.asarray(y), jnp.float64)
        state64 = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a), jnp.float64), net.state_list)

        from deeplearning4j_tpu.nn.multilayer import loss_fn

        def score(p):
            loss, _ = loss_fn(net.conf, p, state64, x64, y64, None, None, None)
            return loss

        analytic = jax.grad(score)(params64)
        flat_analytic = np.asarray(flatten_params(analytic), np.float64)
        flat_params = np.asarray(flatten_params(params64), np.float64)

        n = len(flat_params)
        if subset is not None and subset < n:
            rng = np.random.default_rng(seed)
            indices = rng.choice(n, subset, replace=False)
        else:
            indices = np.arange(n)

        score_jit = jax.jit(lambda flat: score(unflatten_params(params64, flat)))  # lint: adhoc-jit-ok (float64 finite-difference probe outside every dtype policy; never serves, never warm-starts)

        max_err = 0.0
        fails = 0
        for i in indices:
            plus = flat_params.copy()
            plus[i] += eps
            minus = flat_params.copy()
            minus[i] -= eps
            numeric = (float(score_jit(jnp.asarray(plus)))
                       - float(score_jit(jnp.asarray(minus)))) / (2 * eps)
            a = flat_analytic[i]
            denom = max(abs(numeric), abs(a))
            rel = abs(numeric - a) / denom if denom > 0 else 0.0
            if rel > max_rel_error and abs(numeric - a) > min_abs_error:
                fails += 1
                if verbose:
                    log.info("param %d: analytic=%.8g numeric=%.8g "
                             "rel=%.3g", i, a, numeric, rel)
            max_err = max(max_err, rel if abs(numeric - a) > min_abs_error else 0.0)
        if verbose:
            log.info("gradient check: %d params, max rel err %.3g, "
                     "%d failures", len(indices), max_err, fails)
        return fails == 0
