"""ComputationGraph: DAG network with multi-input/multi-output training.

Reference: nn/graph/ComputationGraph.java (2280 LoC) — init:266, fit:670/747,
computeGradientAndScore:952, feedForward:1003, calcBackpropGradients:1174 (reverse topo).

TPU-native: forward walks the topological order inside one traced function; autodiff
produces the reverse-topo backward (the reference's hand-written calcBackpropGradients).
The whole train step (multi-output loss sum + updaters) is one jit-compiled, donated
function, as in MultiLayerNetwork.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import common
from deeplearning4j_tpu.nn.conf.graphconf import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf.vertices import LayerVertex
from deeplearning4j_tpu.nn.multilayer import (
    _AUX_TERMS, LazyScore, _batch_size, _updater_spec,
)
from deeplearning4j_tpu.observability.compile_tracker import (
    global_tracker as _compile_tracker,
)
from deeplearning4j_tpu.observability.flight_recorder import (
    dump_on_unhandled as _dump_on_unhandled,
    global_recorder as _flight_recorder,
)
from deeplearning4j_tpu.observability.watchdog import beat as _wd_beat
from deeplearning4j_tpu.nn.updaters import (
    effective_lr, grads_to_param_dtype, normalize_gradients, updater_init,
    updater_step_with_param,
)
from deeplearning4j_tpu.ops.remat import checkpoint_layer
from deeplearning4j_tpu.utils.pytree import flatten_params, num_params, unflatten_params

Array = jax.Array


@dataclasses.dataclass
class MultiDataSet:
    """Multi-input/multi-output dataset (reference ND4J MultiDataSet)."""

    features: list
    labels: list
    features_masks: Optional[list] = None
    labels_masks: Optional[list] = None

    def num_examples(self) -> int:
        return int(self.features[0].shape[0])


def _graph_regularization(conf, params):
    if not conf.global_conf.use_regularization:
        return jnp.float32(0.0)
    total = jnp.float32(0.0)
    for name, vertex in conf.vertices.items():
        if not isinstance(vertex, LayerVertex) or name not in params:
            continue
        layer = vertex.layer
        for pname in layer.regularizable_params():
            if pname not in params[name]:
                continue
            w = params[name][pname]
            if layer.l1:
                total = total + layer.l1 * jnp.sum(jnp.abs(w))
            if layer.l2:
                total = total + 0.5 * layer.l2 * jnp.sum(w * w)
    return total


def graph_forward(conf: ComputationGraphConfiguration, params: dict, states: dict,
                  inputs: list, *, train: bool, rng: Optional[jax.Array],
                  masks: Optional[list] = None, collect_loss_inputs: bool = False):
    """Walk the DAG in topological order (reference feedForward:1003).

    Masks are routed per input stream: each vertex receives the mask propagated from
    its ancestors (first non-None among its inputs), mirroring the reference's
    per-input mask arrays (ComputationGraph.setLayerMaskArrays).

    Returns (activations dict, new states dict, loss_inputs dict) — loss_inputs maps
    each loss-bearing output vertex to its pre-layer input (for compute_loss), while
    acts[name] always holds the real activation so downstream consumers see the right
    tensor even during training.
    """
    acts: dict[str, Array] = dict(zip(conf.network_inputs, inputs))
    mask_of: dict[str, Optional[Array]] = {name: None for name in conf.network_inputs}
    if masks:
        for i, name in enumerate(conf.network_inputs):
            if i < len(masks):
                mask_of[name] = masks[i]
    new_states: dict[str, dict] = {}
    loss_inputs: dict[str, Array] = {}
    order = conf.topological_order or conf.topo_sort()
    rngs = (jax.random.split(rng, len(order)) if rng is not None
            else [None] * len(order))
    remat = train and conf.global_conf.gradient_checkpointing
    for i, name in enumerate(order):
        vertex = conf.vertices[name]
        srcs = conf.vertex_inputs[name]
        vins = [acts[src] for src in srcs]
        mask = next((mask_of[s] for s in srcs if mask_of.get(s) is not None), None)
        if (collect_loss_inputs and name in conf.network_outputs
                and isinstance(vertex, LayerVertex) and vertex.layer.has_loss()):
            loss_inputs[name] = vins[0]
        # the vertex's name is in the op_name of everything it traces,
        # forward and backward (see multilayer._layer_scope)
        with jax.named_scope(f"layer/{name}"):
            if remat and isinstance(vertex, LayerVertex):
                # checkpointed per layer vertex: backward recomputes this
                # vertex's forward instead of holding its activations
                def f(p, vi, _v=vertex, _s=states.get(name, {}), _r=rngs[i]):
                    return _v.apply(p, _s, vi, train=True, rng=_r, mask=mask)
                y, ns = checkpoint_layer(f)(params.get(name, {}), vins)
            else:
                y, ns = vertex.apply(params.get(name, {}),
                                     states.get(name, {}), vins, train=train,
                                     rng=rngs[i], mask=mask)
        acts[name] = y
        new_states[name] = ns
        mask_of[name] = mask
    return acts, new_states, loss_inputs


def graph_loss(conf, params, states, inputs, labels, rng, fmasks=None, lmasks=None):
    """Sum of output-layer losses + regularization (reference computeGradientAndScore:952)."""
    acts, new_states, loss_inputs = graph_forward(
        conf, params, states, inputs, train=True, rng=rng, masks=fmasks,
        collect_loss_inputs=True)
    with jax.named_scope("loss"):
        total = jnp.float32(0.0)
        for i, out_name in enumerate(conf.network_outputs):
            vertex = conf.vertices[out_name]
            if not (isinstance(vertex, LayerVertex)
                    and vertex.layer.has_loss()):
                raise ValueError(
                    f"Output vertex '{out_name}' has no loss function")
            h = loss_inputs[out_name]
            lmask = lmasks[i] if lmasks else None
            total = total + vertex.layer.compute_loss(
                params[out_name], h, labels[i], lmask)
        total = total + _aux_losses(conf, new_states)
        total = total + _graph_regularization(conf, params)
    return total, new_states


def _aux_losses(conf, new_states):
    """Layer-declared auxiliary objectives (MoE load-balance, an indexer's
    divergence: ``multilayer._AUX_TERMS``), published through the vertex
    state pytree. Shared by the standard and TBPTT train objectives so a MoE
    vertex keeps its balance term under truncated BPTT too (reference
    computeGradientAndScore:952 adds every layer's contribution regardless
    of backprop type)."""
    total = jnp.float32(0.0)
    for name, ns in new_states.items():
        if not isinstance(ns, dict):
            continue
        layer = getattr(conf.vertices[name], "layer", None)
        for term, weight in _AUX_TERMS:
            if term in ns:
                total = total + getattr(layer, weight, 1.0) * ns[term]
    return total


@jax.named_scope("update")
def _apply_graph_updates(conf, params, grads, upd_state, iteration):
    """Per-vertex gradient normalization + updater math (shared by the
    standard and TBPTT train steps), traced under the ``update`` scope."""
    g = conf.global_conf
    grads = grads_to_param_dtype(
        grads, {n: {k: params[n][k] for k in gv} for n, gv in grads.items()})
    new_params = {}
    new_upd = {}
    for name in conf.topological_order:
        vertex = conf.vertices[name]
        g_v = grads.get(name, {})
        if not g_v or not isinstance(vertex, LayerVertex):
            new_params[name] = params.get(name, {})
            new_upd[name] = upd_state.get(name, {})
            continue
        layer = vertex.layer
        g_v = normalize_gradients(g_v, layer.gradient_normalization,
                                  layer.gradient_normalization_threshold or 1.0)
        spec = _updater_spec(layer)
        lr = effective_lr(layer.learning_rate, g.lr_policy, iteration,
                          g.lr_policy_decay_rate, g.lr_policy_power,
                          g.lr_policy_steps, g.lr_schedule, g.max_num_iterations)
        lr_bias = (jnp.float32(layer.bias_learning_rate)
                   if layer.bias_learning_rate is not None else lr)
        p_new, u_new = {}, {}
        for pname, grad in g_v.items():
            this_lr = lr_bias if pname in ("b", "vb", "beta") else lr
            step, ustate = updater_step_with_param(
                spec, grad, params[name][pname], upd_state[name][pname],
                this_lr, iteration)
            p_new[pname] = params[name][pname] - step
            u_new[pname] = ustate
        new_params[name] = p_new
        new_upd[name] = u_new
    return new_params, new_upd


def make_graph_train_step(conf: ComputationGraphConfiguration, *,
                          health: bool = False):
    """``health=True`` appends the health monitor's packed summary vector to
    the return tuple (see make_train_step in multilayer.py)."""
    def train_step(params, states, upd_state, inputs, labels, rng, iteration,
                   fmasks=None, lmasks=None):
        (loss, new_states), grads = jax.value_and_grad(
            lambda p: graph_loss(conf, p, states, inputs, labels, rng, fmasks, lmasks),
            has_aux=True)(params)
        new_params, new_upd = _apply_graph_updates(conf, params, grads,
                                                   upd_state, iteration)
        if health:
            from deeplearning4j_tpu.observability.health import health_terms

            haux = health_terms(grads, params, new_params, loss)
            return new_params, new_states, new_upd, loss, haux
        return new_params, new_states, new_upd, loss

    # a config-declared dtype policy is baked in at trace time (GlobalConf.dtype)
    return common.wrap_with_policy(train_step, conf.global_conf.dtype)


def _is_streaming_lstm(vertex) -> bool:
    from deeplearning4j_tpu.nn.conf.layers.recurrent import LSTM

    return (isinstance(vertex, LayerVertex) and isinstance(vertex.layer, LSTM)
            and not type(vertex.layer).__name__.startswith(
                "GravesBidirectional"))


def _init_graph_rnn_states(conf, batch: int, dtype) -> dict:
    states = {}
    for name, vertex in conf.vertices.items():
        if _is_streaming_lstm(vertex):
            h = vertex.layer.n_out
            states[name] = {"h": jnp.zeros((batch, h), dtype),
                            "c": jnp.zeros((batch, h), dtype)}
        else:
            states[name] = {}
    return states


def graph_forward_streaming(conf, params, states, rnn_states, inputs, *,
                            train: bool, rng, masks=None,
                            collect_loss_inputs: bool = False,
                            truncate: bool = False):
    """DAG walk threading LSTM streaming state across calls (reference
    ComputationGraph.rnnTimeStep:1788 / rnnActivateUsingStoredState:1955).

    ``truncate=True`` stop-gradients the carried state at the chunk boundary
    — the TBPTT truncation (reference doTruncatedBPTT semantics on graphs,
    ComputationGraph.fit -> rnnUpdateStateWithTBPTTState:2032).
    Returns (acts, new_states, loss_inputs, new_rnn_states).
    """
    acts: dict = dict(zip(conf.network_inputs, inputs))
    mask_of: dict = {name: None for name in conf.network_inputs}
    if masks:
        for i, name in enumerate(conf.network_inputs):
            if i < len(masks):
                mask_of[name] = masks[i]
    new_states: dict = {}
    new_rnn: dict = {}
    loss_inputs: dict = {}
    order = conf.topological_order or conf.topo_sort()
    rngs = (jax.random.split(rng, len(order)) if rng is not None
            else [None] * len(order))
    for i, name in enumerate(order):
        vertex = conf.vertices[name]
        srcs = conf.vertex_inputs[name]
        vins = [acts[src] for src in srcs]
        mask = next((mask_of[s] for s in srcs if mask_of.get(s) is not None),
                    None)
        if (collect_loss_inputs and name in conf.network_outputs
                and isinstance(vertex, LayerVertex)
                and vertex.layer.has_loss()):
            loss_inputs[name] = vins[0]
        if _is_streaming_lstm(vertex):
            y, rs = vertex.layer.apply_streaming(
                params.get(name, {}), rnn_states.get(name, {}), vins[0],
                mask=mask)
            if truncate:
                rs = jax.tree_util.tree_map(jax.lax.stop_gradient, rs)
            new_rnn[name] = rs
            ns = states.get(name, {})
        else:
            y, ns = vertex.apply(params.get(name, {}), states.get(name, {}),
                                 vins, train=train, rng=rngs[i], mask=mask)
            new_rnn[name] = rnn_states.get(name, {})
        acts[name] = y
        new_states[name] = ns
        mask_of[name] = mask
    return acts, new_states, loss_inputs, new_rnn


def make_graph_tbptt_step(conf: ComputationGraphConfiguration):
    """TBPTT train step for graphs: threads LSTM state across time chunks,
    truncating gradients at chunk boundaries (reference ComputationGraph
    doTruncatedBPTT path, fit:747 -> calcBackpropGradients with tbptt)."""

    def tbptt_step(params, states, upd_state, rnn_states, inputs, labels, rng,
                   iteration, fmasks=None, lmasks=None):
        def lf(p):
            _, new_states, loss_inputs, new_rnn = graph_forward_streaming(
                conf, p, states, rnn_states, inputs, train=True, rng=rng,
                masks=fmasks, collect_loss_inputs=True, truncate=True)
            total = jnp.float32(0.0)
            for i, out_name in enumerate(conf.network_outputs):
                vertex = conf.vertices[out_name]
                if not (isinstance(vertex, LayerVertex)
                        and vertex.layer.has_loss()):
                    raise ValueError(
                        f"Output vertex '{out_name}' has no loss function")
                lmask = lmasks[i] if lmasks else None
                total = total + vertex.layer.compute_loss(
                    p[out_name], loss_inputs[out_name], labels[i], lmask)
            total = total + _aux_losses(conf, new_states)
            return total + _graph_regularization(conf, p), (new_states, new_rnn)

        (loss, (new_states, new_rnn)), grads = jax.value_and_grad(
            lf, has_aux=True)(params)
        new_params, new_upd = _apply_graph_updates(conf, params, grads,
                                                   upd_state, iteration)
        return new_params, new_states, new_upd, new_rnn, loss

    return common.wrap_with_policy(tbptt_step, conf.global_conf.dtype)


def make_graph_multistep_train_step(conf: ComputationGraphConfiguration, *,
                                    health: bool = False):
    """K fused graph train steps per host dispatch via `lax.scan`.

    ``inputs_stack``/``labels_stack`` are lists of ``(K, B, ...)`` arrays (one
    per graph input/output). See make_multistep_train_step in multilayer.py
    for the rationale (dispatch amortization on TPU) and the ``health``
    variant's stacked ``(K, 4)`` summary output."""
    step = make_graph_train_step(conf, health=health)

    def dl4j_train_ksteps(params, states, upd_state, inputs_stack,
                          labels_stack, rng, iteration0):
        def body(carry, batch):
            p, s, u, it = carry
            xs, ys = batch
            key = jax.random.fold_in(rng, it)
            if health:
                p, s, u, loss, haux = step(p, s, u, xs, ys, key, it)
                return (p, s, u, it + 1), (loss, haux)
            p, s, u, loss = step(p, s, u, xs, ys, key, it)
            return (p, s, u, it + 1), loss

        (p, s, u, _), out = jax.lax.scan(
            body, (params, states, upd_state, iteration0),
            (list(inputs_stack), list(labels_stack)))
        if health:
            losses, hauxs = out
            return p, s, u, losses, hauxs
        return p, s, u, out

    # the compiled module's name, as in make_multistep_train_step
    return dl4j_train_ksteps


def _ancestor_set(conf, target: str) -> set:
    """All vertices the target transitively depends on (inputs included)."""
    anc: set = set()
    stack = list(conf.vertex_inputs.get(target, []))
    while stack:
        n = stack.pop()
        if n in anc:
            continue
        anc.add(n)
        stack.extend(conf.vertex_inputs.get(n, []))
    return anc


def eval_forward_to_vertex(conf, params, states, inputs, name: str):
    """Eval-mode forward of ``name``'s ancestors only; returns the vertex's
    (first) input activation. ONE walk shared by the pretrain train step and
    the graph pretrain gradient checker so both always see the same forward."""
    anc = _ancestor_set(conf, name)
    order = [n for n in (conf.topological_order or conf.topo_sort())
             if n in anc]
    acts = dict(zip(conf.network_inputs, inputs))
    for n in order:
        if n in acts:
            continue
        vins = [acts[s] for s in conf.vertex_inputs[n]]
        y, _ = conf.vertices[n].apply(params.get(n, {}), states.get(n, {}),
                                      vins, train=False, rng=None)
        acts[n] = y
    return acts[conf.vertex_inputs[name][0]]


def make_graph_pretrain_step(conf: ComputationGraphConfiguration, name: str):
    """Unsupervised pretrain step for one graph vertex (reference
    ComputationGraph.pretrainLayer:540): evaluate the vertex's ancestors in
    eval mode, stop the gradient at the vertex input, and minimize the
    vertex layer's pretrain objective — only that vertex's params move."""
    g = conf.global_conf
    layer = conf.vertices[name].layer

    def pretrain_step(params, states, vertex_upd_state, inputs, rng, iteration):
        h = jax.lax.stop_gradient(
            eval_forward_to_vertex(conf, params, states, inputs, name))

        def lf(p):
            return layer.pretrain_loss(p, h, rng=rng)

        loss, grads = jax.value_and_grad(lf)(params[name])
        grads = grads_to_param_dtype(grads, params[name])
        grads = normalize_gradients(grads, layer.gradient_normalization,
                                    layer.gradient_normalization_threshold or 1.0)
        spec = _updater_spec(layer)
        lr = effective_lr(layer.learning_rate, g.lr_policy, iteration,
                          g.lr_policy_decay_rate, g.lr_policy_power,
                          g.lr_policy_steps, g.lr_schedule, g.max_num_iterations)
        p_new, u_new = {}, {}
        for pname, grad in grads.items():
            step, ustate = updater_step_with_param(
                spec, grad, params[name][pname], vertex_upd_state[pname],
                lr, iteration)
            p_new[pname] = params[name][pname] - step
            u_new[pname] = ustate
        return p_new, u_new, loss

    return common.wrap_with_policy(pretrain_step, g.dtype)


class ComputationGraph(LazyScore):
    """Stateful shell (reference nn/graph/ComputationGraph.java)."""

    _step_builder = staticmethod(make_graph_train_step)
    _multistep_builder = staticmethod(make_graph_multistep_train_step)
    _fit_path = "graph"

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.params_list: Optional[dict] = None   # name -> params dict
        self.state_list: Optional[dict] = None
        self.updater_state: Optional[dict] = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: list = []
        self.score_value = float("nan")
        self._rng = None
        self._jit_cache: dict = {}
        self._rnn_state: Optional[dict] = None  # streaming rnn_time_step state

    # ------------------------------------------------------------------ lifecycle
    def init(self, seed: Optional[int] = None) -> "ComputationGraph":
        t0_ns = time.time_ns()
        g = self.conf.global_conf
        key = jax.random.PRNGKey(g.seed if seed is None else seed)
        self._rng = jax.random.fold_in(key, 0xC6)
        order = self.conf.topological_order or self.conf.topo_sort()
        self.conf.topological_order = order
        keys = jax.random.split(key, max(len(order), 1))
        # propagate input types for init
        types: dict = {}
        if self.conf.input_types:
            types.update(zip(self.conf.network_inputs, self.conf.input_types))
        self.params_list = {}
        self.state_list = {}
        for i, name in enumerate(order):
            vertex = self.conf.vertices[name]
            in_types = [types.get(src) for src in self.conf.vertex_inputs[name]]
            self.params_list[name] = vertex.init_params(keys[i], in_types)
            self.state_list[name] = vertex.init_state(in_types)
            try:
                types[name] = vertex.output_type(in_types)
            except Exception:
                types[name] = None
        self.updater_state = {
            name: {pname: updater_init(_updater_spec(self.conf.vertices[name].layer), p)
                   for pname, p in params.items()}
            if isinstance(self.conf.vertices[name], LayerVertex) else {}
            for name, params in self.params_list.items()
        }
        self._book_init(t0_ns)
        return self

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    # ------------------------------------------------------------------ params API
    def params(self) -> Array:
        return flatten_params(self.params_list, jnp.float32)

    def set_params(self, flat: Array) -> None:
        self.params_list = unflatten_params(self.params_list, flat)

    def num_params(self) -> int:
        return num_params(self.params_list)

    # ------------------------------------------------------------------ inference
    def output(self, *inputs) -> list:
        """Forward pass returning all network outputs (reference output:1520)."""
        self._require_init()
        xs = [jnp.asarray(x) for x in inputs]
        fn = self._jit("output", self._output_pure)
        outs, _ = fn(self.params_list, self.state_list, xs)
        return outs

    def _output_pure(self, params, states, xs):
        acts, ns, _ = graph_forward(self.conf, params, states, xs, train=False,
                                    rng=None)
        return [acts[o] for o in self.conf.network_outputs], ns

    def score(self, mds: MultiDataSet) -> float:
        self._require_init()
        xs = [jnp.asarray(f) for f in mds.features]
        ys = [jnp.asarray(l) for l in mds.labels]
        fn = self._jit("score", self._score_pure)
        return float(fn(self.params_list, self.state_list, xs, ys))

    def _score_pure(self, params, states, xs, ys):
        # evaluation loss: eval-mode forward (no dropout, running BN stats,
        # no MoE aux term) + data losses + regularization — mirrors
        # MultiLayerNetwork.score and the reference's score():1704 semantics
        conf = self.conf
        _, _, loss_inputs = graph_forward(conf, params, states, xs,
                                          train=False, rng=None,
                                          collect_loss_inputs=True)
        total = jnp.float32(0.0)
        for i, out_name in enumerate(conf.network_outputs):
            vertex = conf.vertices[out_name]
            if not (isinstance(vertex, LayerVertex) and vertex.layer.has_loss()):
                raise ValueError(f"Output vertex '{out_name}' has no loss function")
            total = total + vertex.layer.compute_loss(
                params[out_name], loss_inputs[out_name], ys[i], None)
        return total + _graph_regularization(conf, params)

    # ------------------------------------------------------------------ training
    @_dump_on_unhandled("ComputationGraph.fit")
    def fit(self, data, labels=None, *, epochs: int = 1) -> None:
        """Fit on a MultiDataSet, DataSet, iterator, or (inputs, labels) lists
        (reference fit:670/747); the loop itself is ``LazyScore``'s."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        if isinstance(data, (MultiDataSet, DataSet)):
            self._fit_arrays(*self._batch_of(data), epochs)
        elif labels is not None:
            xs = list(data if isinstance(data, (list, tuple)) else [data])
            ys = list(labels if isinstance(labels, (list, tuple)) else [labels])
            self._fit_arrays(xs, ys, None, None, epochs)
        else:
            self.fit_iterator(data, epochs=epochs)

    def _batch_of(self, ds) -> tuple:
        """A DataSet or MultiDataSet as (xs, ys, fmasks, lmasks) lists."""
        if isinstance(ds, MultiDataSet):
            return (list(ds.features), list(ds.labels), ds.features_masks,
                    ds.labels_masks)
        fm = [ds.features_mask] if ds.features_mask is not None else None
        lm = [ds.labels_mask] if ds.labels_mask is not None else None
        return [ds.features], [ds.labels], fm, lm

    def _tbptt_active(self) -> bool:
        return (self.conf.backprop_type == "TruncatedBPTT"
                and any(_is_streaming_lstm(v)
                        for v in self.conf.vertices.values()))

    # ------------------------------------------------------------------ pretrain
    def pretrain(self, iterator) -> None:
        """Greedy layerwise unsupervised pretraining over every pretrainable
        vertex in topological order (reference ComputationGraph.pretrain:509):
        earlier vertices are frozen features for later ones."""
        from deeplearning4j_tpu.nn.conf.layers.base import PretrainLayer

        for name in self.conf.topological_order or self.conf.topo_sort():
            vertex = self.conf.vertices[name]
            if (isinstance(vertex, LayerVertex)
                    and isinstance(vertex.layer, PretrainLayer)):
                self.pretrain_layer(name, iterator)

    def pretrain_layer(self, name: str, iterator) -> None:
        """Pretrain ONE vertex layer unsupervised (reference
        ComputationGraph.pretrainLayer:540). Ancestor vertices run in eval
        mode to produce its input; only the named vertex's params update."""
        from deeplearning4j_tpu.nn.conf.layers.base import PretrainLayer

        self._require_init()
        if name not in self.conf.vertices:
            raise ValueError(
                f"Unknown vertex '{name}' — graph vertices: "
                f"{sorted(self.conf.vertices)}")
        vertex = self.conf.vertices[name]
        if not (isinstance(vertex, LayerVertex)
                and isinstance(vertex.layer, PretrainLayer)):
            raise ValueError(
                f"Vertex '{name}' is not pretrainable — layerwise pretraining "
                "needs an unsupervised layer (VAE, RBM, AutoEncoder)")
        step = self._jit(f"pretrain:{name}",
                         make_graph_pretrain_step(self.conf, name))
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            xs, _, _, _ = self._batch_of(ds)
            xs = [jnp.asarray(x) for x in xs]
            (self.params_list[name], self.updater_state[name], loss) = step(
                self.params_list, self.state_list, self.updater_state[name],
                xs, self._next_rng(), jnp.int32(self.iteration))
            self.score_value = loss  # synced lazily (LazyScore)

    # ------------------------------------------------------------------ evaluation
    def evaluate(self, iterator, labels_list=None, top_n: int = 1):
        """Evaluate the network's outputs against a (Multi)DataSet iterator
        (reference ComputationGraph.evaluate:2230,2253).

        Label masks are threaded per output stream — masked timesteps do not
        count — and every network output is scored against its matching label
        array into one accumulated Evaluation (single-output graphs behave
        exactly as before). ``labels_list``/``top_n`` attach class-label names
        and top-N accuracy, as in MultiLayerNetwork.evaluate.
        """
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        ev = Evaluation(labels=labels_list, top_n=top_n)
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            feats, labels, fmasks, lmasks = self._batch_of(ds)
            outs = self._output_for_eval(feats, fmasks)
            n_cls = np.asarray(labels[0]).shape[-1]
            for i, out in enumerate(outs):
                if i >= len(labels):
                    break
                if np.asarray(labels[i]).shape[-1] != n_cls:
                    # one Evaluation holds one confusion matrix; streams with
                    # a different class count need their own pass (evaluate a
                    # single-output view or use eval/ directly)
                    continue
                lm = (np.asarray(lmasks[i])
                      if lmasks and i < len(lmasks) and lmasks[i] is not None
                      else None)
                ev.eval(np.asarray(labels[i]), np.asarray(out), mask=lm)
        return ev

    def _output_for_eval(self, feats, fmasks):
        """Eval-mode forward that honors feature masks (evaluate's path;
        output() stays the mask-free public inference entry)."""
        self._require_init()
        xs = [jnp.asarray(f) for f in feats]
        if fmasks is None:
            fn = self._jit("output", self._output_pure)
            outs, _ = fn(self.params_list, self.state_list, xs)
            return outs
        ms = [jnp.asarray(m) if m is not None else None for m in fmasks]
        fn = self._jit("output_masked", self._output_masked_pure)
        outs, _ = fn(self.params_list, self.state_list, xs, ms)
        return outs

    def _output_masked_pure(self, params, states, xs, masks):
        acts, ns, _ = graph_forward(self.conf, params, states, xs, train=False,
                                    rng=None, masks=masks)
        return [acts[o] for o in self.conf.network_outputs], ns

    # ------------------------------------------------------------------ TBPTT
    def _fit_tbptt(self, xs, ys, fmasks=None, lmasks=None) -> None:
        """Truncated BPTT on graphs (reference ComputationGraph fit with
        BackpropType.TruncatedBPTT): slice every input/label/mask along the
        time axis into tbptt_fwd_length chunks; LSTM-vertex state carries
        across chunks via stop_gradient (the truncation). Time axis = 1."""
        xs = [jnp.asarray(x) for x in xs]
        ys = [jnp.asarray(y) for y in ys]
        self.last_batch_size = _batch_size(xs)
        T = xs[0].shape[1]
        L = self.conf.tbptt_fwd_length
        n_chunks = max(1, math.ceil(T / L))
        step = self._jit("tbptt_step", make_graph_tbptt_step(self.conf))
        rnn_state = _init_graph_rnn_states(self.conf, xs[0].shape[0],
                                           xs[0].dtype)
        for c in range(n_chunks):
            sl = slice(c * L, min((c + 1) * L, T))
            xc = [x[:, sl] for x in xs]
            yc = [y[:, sl] for y in ys]
            fm = [m[:, sl] for m in fmasks] if fmasks else None
            lm = [m[:, sl] for m in lmasks] if lmasks else None
            (self.params_list, self.state_list, self.updater_state, rnn_state,
             loss) = step(self.params_list, self.state_list,
                          self.updater_state, rnn_state, xc, yc,
                          self._next_rng(), jnp.int32(self.iteration), fm, lm)
            _compile_tracker().note_step(fn=f"{type(self).__name__}.tbptt_step")
            _flight_recorder().record(
                "step", path=f"{type(self).__name__}.tbptt_step",
                it=self.iteration, batch=self.last_batch_size)
            self.score_value = loss  # synced lazily (LazyScore)
            self.iteration += 1
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration)
            _wd_beat(self.iteration)

    # ------------------------------------------------------------------ rnn API
    def rnn_time_step(self, *inputs) -> list:
        """Streaming inference carrying LSTM-vertex hidden state across calls
        (reference ComputationGraph.rnnTimeStep:1788). Each input: [B,T,F]
        (T may be 1). Returns the list of network outputs."""
        self._require_init()
        xs = [jnp.asarray(x) for x in inputs]
        if self._rnn_state is None:
            self._rnn_state = _init_graph_rnn_states(self.conf, xs[0].shape[0],
                                                     xs[0].dtype)
        fn = self._jit("rnn_time_step", self._rnn_step_pure)
        outs, self._rnn_state = fn(self.params_list, self.state_list,
                                   self._rnn_state, xs)
        return outs

    def _rnn_step_pure(self, params, states, rnn_states, xs):
        acts, _, _, new_rnn = graph_forward_streaming(
            self.conf, params, states, rnn_states, xs, train=False, rng=None)
        return [acts[o] for o in self.conf.network_outputs], new_rnn

    def rnn_get_previous_state(self):
        """Per-vertex streaming LSTM state (reference
        ComputationGraph.rnnGetPreviousState:1873)."""
        return self._rnn_state

    def rnn_set_previous_state(self, state) -> None:
        """Install streaming state (reference rnnSetPreviousState:1912)."""
        self._rnn_state = (jax.tree_util.tree_map(jnp.asarray, state)
                           if state is not None else None)

    def rnn_clear_previous_state(self) -> None:
        self._rnn_state = None

    def clone(self) -> "ComputationGraph":
        """Deep copy with REAL buffer copies (see MultiLayerNetwork.clone:
        the fused fit path donates param buffers to XLA, so clones must not
        alias arrays). Reference ComputationGraph.clone:1249."""
        import copy

        net = ComputationGraph(copy.deepcopy(self.conf))
        cp = lambda a: jnp.array(a)
        net.params_list = jax.tree_util.tree_map(cp, self.params_list)
        net.state_list = jax.tree_util.tree_map(cp, self.state_list)
        net.updater_state = jax.tree_util.tree_map(cp, self.updater_state)
        net.iteration = self.iteration
        net.epoch = self.epoch
        net._rng = self._rng
        if self._rnn_state is not None:  # mid-stream serving handoff
            net._rnn_state = jax.tree_util.tree_map(cp, self._rnn_state)
        return net

    def score_examples(self, data, add_regularization: bool = False):
        """Per-example loss scores, un-reduced, summed over the graph's
        outputs (reference ComputationGraph.scoreExamples:1485/1502).
        Feature masks route through the forward walk, label masks weight
        each example's own loss — as in fit()."""
        self._require_init()
        xs, ys, fms, lms = self._batch_of(data)
        asarray_opt = lambda m: jnp.asarray(m) if m is not None else None
        fn = self._jit("score_examples", self._score_examples_pure)
        per = fn(self.params_list, self.state_list,
                 [jnp.asarray(x) for x in xs], [jnp.asarray(y) for y in ys],
                 [asarray_opt(m) for m in fms] if fms else None,
                 [asarray_opt(m) for m in lms] if lms else None)
        if add_regularization:
            per = per + _graph_regularization(self.conf, self.params_list)
        return np.asarray(per)

    def _score_examples_pure(self, params, states, xs, ys, fms, lms):
        conf = self.conf
        _, _, loss_inputs = graph_forward(conf, params, states, xs,
                                          train=False, rng=None, masks=fms,
                                          collect_loss_inputs=True)
        total = None
        for i, out_name in enumerate(conf.network_outputs):
            vertex = conf.vertices[out_name]
            if not (isinstance(vertex, LayerVertex) and vertex.layer.has_loss()):
                raise ValueError(
                    f"Output vertex '{out_name}' has no loss function")
            layer = vertex.layer
            lm = lms[i] if lms and i < len(lms) and lms[i] is not None else None

            def one(hi, yi, mi=None, _l=layer, _n=out_name):
                return _l.compute_loss(params[_n], hi[None], yi[None],
                                       mi[None] if mi is not None else None)

            per = (jax.vmap(one)(loss_inputs[out_name], ys[i], lm)
                   if lm is not None
                   else jax.vmap(one)(loss_inputs[out_name], ys[i]))
            total = per if total is None else total + per
        return total

    def gradient_and_score(self, xs, ys):
        self._require_init()
        xs = [jnp.asarray(x) for x in xs]
        ys = [jnp.asarray(y) for y in ys]

        def lf(p):
            loss, _ = graph_loss(self.conf, p, self.state_list, xs, ys, None)
            return loss

        loss, grads = jax.value_and_grad(lf)(self.params_list)
        return grads, float(loss)
