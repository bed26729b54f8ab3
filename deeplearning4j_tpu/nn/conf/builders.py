"""NeuralNetConfiguration: global training hyperparameters + fluent builder DSL.

Reference: nn/conf/NeuralNetConfiguration.java:478-1100 (Builder), :194-327 (ListBuilder).
Builder method names match the reference's (snake_cased) so configs translate 1:1:

    conf = (NeuralNetConfiguration.builder()
            .seed(123).learning_rate(0.1).updater("nesterovs").momentum(0.9)
            .weight_init("xavier").activation("relu")
            .list()
            .layer(DenseLayer(n_out=500))
            .layer(OutputLayer(n_out=10, loss="mcxent", activation="softmax"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1))
            .backprop(True).pretrain(False)
            .build())

Global defaults are *baked into* each layer at build() (the reference clones the config
per layer the same way), so a serialized MultiLayerConfiguration is self-contained.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.serde import register_config
from deeplearning4j_tpu.nn.conf.layers.base import Layer
from deeplearning4j_tpu.nn.conf.preprocessors import InputPreProcessor, infer_preprocessor


@register_config("GlobalConf")
@dataclasses.dataclass
class GlobalConf:
    """Network-wide defaults (reference NeuralNetConfiguration fields :84-121)."""

    seed: int = 12345
    optimization_algo: str = "stochastic_gradient_descent"
    iterations: int = 1                 # updates per presented minibatch (DL4J semantics)
    learning_rate: float = 0.1
    bias_learning_rate: Optional[float] = None
    lr_policy: Optional[str] = None     # exponential|inverse|poly|sigmoid|step|schedule
    lr_policy_decay_rate: float = 0.0
    lr_policy_power: float = 0.0
    lr_policy_steps: float = 1.0
    lr_schedule: Optional[dict] = None
    max_num_iterations: int = 1         # for poly policy
    updater: str = "sgd"
    momentum: float = 0.9
    momentum_schedule: Optional[dict] = None
    rho: float = 0.95
    rms_decay: float = 0.95
    adam_mean_decay: float = 0.9
    adam_var_decay: float = 0.999
    epsilon: float = 1e-8
    activation: str = "sigmoid"
    weight_init: str = "xavier"
    dist: Optional[dict] = None
    bias_init: float = 0.0
    l1: float = 0.0
    l2: float = 0.0
    dropout: float = 0.0
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    minibatch: bool = True
    mini_batch: bool = True
    use_regularization: bool = False
    max_num_line_search_iterations: int = 5
    #: rematerialize per-layer activations in backward (jax.checkpoint
    #: through ``ops/remat.py::checkpoint_layer``): trades recompute FLOPs
    #: for activation HBM — the TPU-native memory lever for
    #: deep/long-sequence models (no reference equivalent; the JVM runtime
    #: keeps all activations). A layer keeps its input and its attention
    #: kernels' outputs, which are not made again: per decoder block
    #: tokens x heads x value width x 2 B of the flash core's output, and
    #: B x T x T bytes of an indexer's int8 selection, until its backward
    gradient_checkpointing: bool = False
    #: per-network dtype policy, serialized with the config (the reference's
    #: one global Nd4j data type, made declarative): None -> whatever global
    #: policy is active (common.set_policy); "float32"; "bfloat16" (bf16
    #: matmul/conv, f32 activations); "bfloat16_full" (bf16 activations too,
    #: f32 params/norm-stats/losses — common.full_bf16_policy semantics)
    dtype: Optional[str] = None


def validate_global_conf(g: GlobalConf) -> None:
    """Fail fast on config-string typos at build time, not first trace."""
    if g.dtype is not None:
        from deeplearning4j_tpu import common
        common.resolve_policy(g.dtype)  # raises ValueError with known names


_LAYER_INHERIT_FIELDS = (
    "activation", "weight_init", "dist", "l1", "l2", "dropout",
    "learning_rate", "bias_learning_rate", "updater", "momentum", "rho", "rms_decay",
    "adam_mean_decay", "adam_var_decay", "epsilon",
    "gradient_normalization", "gradient_normalization_threshold",
)


def bake_layer_defaults(layer: Layer, g: GlobalConf) -> None:
    """Fill a layer's None fields from global defaults (reference config cloning)."""
    for f in _LAYER_INHERIT_FIELDS:
        if getattr(layer, f, None) is None:
            gval = getattr(g, f, None)
            if f == "learning_rate":
                gval = g.learning_rate
            if f == "bias_learning_rate":
                gval = g.bias_learning_rate if g.bias_learning_rate is not None else g.learning_rate
                if getattr(layer, "learning_rate", None) is not None:
                    gval = layer.learning_rate
            setattr(layer, f, gval)
    if layer.bias_init is None:
        layer.bias_init = g.bias_init


class NeuralNetConfiguration:
    """Namespace mirroring the reference class; use NeuralNetConfiguration.builder()."""

    @staticmethod
    def builder() -> "Builder":
        return Builder()


class Builder:
    def __init__(self):
        self._g = GlobalConf()

    def __getattr__(self, name):
        """Fluent setter for any GlobalConf field: .seed(1).learning_rate(0.1)..."""
        if name.startswith("_"):
            raise AttributeError(name)
        fields = {f.name for f in dataclasses.fields(GlobalConf)}
        if name in fields:
            def setter(value):
                setattr(self._g, name, value)
                if name == "mini_batch":
                    self._g.minibatch = value
                return self
            return setter
        # aliases matching reference camelCase conventions
        aliases = {
            "regularization": "use_regularization",
            "optimizationAlgo": "optimization_algo",
        }
        if name in aliases:
            def setter(value):
                setattr(self._g, aliases[name], value)
                return self
            return setter
        raise AttributeError(f"No config field '{name}'")

    def list(self) -> "ListBuilder":
        return ListBuilder(self._g)

    def graph_builder(self):
        from deeplearning4j_tpu.nn.conf.graphconf import GraphBuilder
        return GraphBuilder(self._g)

    def global_conf(self) -> GlobalConf:
        return self._g


class ListBuilder:
    """Sequential-network builder (reference NeuralNetConfiguration.ListBuilder:194-327)."""

    def __init__(self, g: GlobalConf):
        self._g = g
        self._layers: list[Layer] = []
        self._preprocessors: dict[int, InputPreProcessor] = {}
        self._input_type: Optional[InputType] = None
        self._backprop = True
        self._pretrain = False
        self._backprop_type = "Standard"
        self._tbptt_fwd = 20
        self._tbptt_back = 20

    def layer(self, idx_or_layer, maybe_layer: Optional[Layer] = None) -> "ListBuilder":
        layer = maybe_layer if maybe_layer is not None else idx_or_layer
        if maybe_layer is not None:
            assert idx_or_layer == len(self._layers), "layers must be added in order"
        self._layers.append(layer)
        return self

    def input_pre_processor(self, idx: int, pp: InputPreProcessor) -> "ListBuilder":
        self._preprocessors[idx] = pp
        return self

    def set_input_type(self, itype: InputType) -> "ListBuilder":
        self._input_type = itype
        return self

    def backprop(self, flag: bool) -> "ListBuilder":
        self._backprop = flag
        return self

    def pretrain(self, flag: bool) -> "ListBuilder":
        self._pretrain = flag
        return self

    def backprop_type(self, t: str) -> "ListBuilder":
        self._backprop_type = t
        return self

    def t_bptt_forward_length(self, n: int) -> "ListBuilder":
        self._tbptt_fwd = n
        return self

    def t_bptt_backward_length(self, n: int) -> "ListBuilder":
        self._tbptt_back = n
        return self

    def build(self):
        from deeplearning4j_tpu.nn.conf.multilayer import MultiLayerConfiguration

        validate_global_conf(self._g)
        for layer in self._layers:
            bake_layer_defaults(layer, self._g)

        # propagate input types: infer preprocessors + n_in per layer
        if self._input_type is not None:
            cur = self._input_type
            for i, layer in enumerate(self._layers):
                if i not in self._preprocessors:
                    pp = infer_preprocessor(cur, layer)
                    if pp is not None:
                        self._preprocessors[i] = pp
                if i in self._preprocessors:
                    cur = self._preprocessors[i].output_type(cur)
                layer.set_n_in(cur)
                cur = layer.output_type(cur)

        return MultiLayerConfiguration(
            global_conf=self._g,
            layers=self._layers,
            preprocessors={str(k): v for k, v in self._preprocessors.items()},
            input_type=self._input_type,
            backprop=self._backprop,
            pretrain=self._pretrain,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
        )
