"""Layer config taxonomy — the serializable layer DSL.

Capability parity with the reference's 19 layer-config classes under
`nn/conf/layers/*` (deeplearning4j-core; SURVEY.md §2.2 'Config DSL + serde'):
Dense, Convolution, Subsampling, BatchNormalization, LRN, GravesLSTM,
GravesBidirectionalLSTM, GRU, RBM, AutoEncoder, Embedding, Activation,
Dropout, Output, RnnOutput (+ GlobalPooling and Loss layers).

Configs are pure data (registered for JSON/YAML round-trip). Unset fields
(None) inherit net-level defaults at build time — mirroring the reference's
`NeuralNetConfiguration.Builder.layer(...)` global->layer resolution.
Each config also implements `get_output_type(input_type)` for the
ConvolutionLayerSetup-style automatic shape inference, and `set_n_in` so the
builder can wire n_in from upstream output shapes.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, List, Optional, Tuple

from .inputs import (ConvolutionalInputType, FeedForwardInputType, InputType,
                     RecurrentInputType)
from .serde import register
from ..updater.updaters import UpdaterConfig


def _require(conf, *names: str) -> None:
    """Fields that are a model's own widths carry no default."""
    missing = [n for n in names if getattr(conf, n) is None]
    if missing:
        raise ValueError(f"{type(conf).__name__} needs " + ", ".join(missing))


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (list, tuple)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


@dataclass
class Layer:
    """Abstract base layer config; every field may be None = inherit."""

    name: Optional[str] = None
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    dist: Optional[Any] = None
    dropout: Optional[float] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    bias_init: Optional[float] = None
    learning_rate: Optional[float] = None
    bias_learning_rate: Optional[float] = None
    updater: Optional[UpdaterConfig] = None
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: Optional[float] = None

    # -- shape inference hooks -------------------------------------------------
    def set_n_in(self, input_type: InputType) -> None:
        """Set this layer's fan-in from the upstream output type (no-op default)."""

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    def is_pretrain_layer(self) -> bool:
        return False

    def clone(self) -> "Layer":
        return dataclasses.replace(self)


@dataclass
class FeedForwardLayer(Layer):
    n_in: Optional[int] = None
    n_out: Optional[int] = None

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            self.n_in = input_type.flat_size()

    def get_output_type(self, input_type: InputType) -> InputType:
        if isinstance(input_type, RecurrentInputType):
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)


@register
@dataclass
class DenseLayer(FeedForwardLayer):
    """Fully connected layer (reference nn/conf/layers/DenseLayer.java)."""


@register
@dataclass
class OutputLayer(FeedForwardLayer):
    """Output layer with loss (reference nn/conf/layers/OutputLayer.java)."""

    loss: str = "negativeloglikelihood"


@register
@dataclass
class RnnOutputLayer(FeedForwardLayer):
    """Per-timestep output layer (reference nn/conf/layers/RnnOutputLayer.java)."""

    loss: str = "mcxent"
    # dtype the logits (and what follows them) are computed in, whatever
    # the net's compute dtype: "float32" under a bfloat16 net is a small
    # vocabulary's `fp32_logits`. None = the net's compute dtype
    logits_dtype: Optional[str] = None

    def get_output_type(self, input_type: InputType) -> InputType:
        ts = input_type.timesteps if isinstance(input_type, RecurrentInputType) else None
        return InputType.recurrent(self.n_out, ts)


@register
@dataclass
class LossLayer(Layer):
    """Loss-only layer, no params (reference LossLayer)."""

    loss: str = "mse"


@register
@dataclass
class ConvolutionLayer(FeedForwardLayer):
    """2D convolution, NHWC (reference nn/conf/layers/ConvolutionLayer.java).

    n_in = input channels, n_out = output channels.
    """

    kernel_size: Tuple[int, int] = (5, 5)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"  # truncate | same
    dilation: Tuple[int, int] = (1, 1)

    def __post_init__(self):
        self.kernel_size = _pair(self.kernel_size)
        self.stride = _pair(self.stride)
        self.padding = _pair(self.padding)
        self.dilation = _pair(self.dilation)

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            if not isinstance(input_type, ConvolutionalInputType):
                raise ValueError(f"ConvolutionLayer expects convolutional input, got {input_type}")
            self.n_in = input_type.channels

    def get_output_type(self, input_type: InputType) -> InputType:
        if not isinstance(input_type, ConvolutionalInputType):
            raise ValueError(f"ConvolutionLayer expects convolutional input, got {input_type}")
        h, w = _conv_out_hw(input_type.height, input_type.width, self.kernel_size,
                            self.stride, self.padding, self.convolution_mode, self.dilation)
        return InputType.convolutional(h, w, self.n_out)


@register
@dataclass
class SubsamplingLayer(Layer):
    """Pooling layer (reference nn/conf/layers/SubsamplingLayer.java)."""

    pooling_type: str = "max"  # max | avg | sum | pnorm
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    pnorm: int = 2

    def __post_init__(self):
        self.kernel_size = _pair(self.kernel_size)
        self.stride = _pair(self.stride)
        self.padding = _pair(self.padding)

    def get_output_type(self, input_type: InputType) -> InputType:
        if not isinstance(input_type, ConvolutionalInputType):
            raise ValueError(f"SubsamplingLayer expects convolutional input, got {input_type}")
        h, w = _conv_out_hw(input_type.height, input_type.width, self.kernel_size,
                            self.stride, self.padding, self.convolution_mode, (1, 1))
        return InputType.convolutional(h, w, input_type.channels)


@register
@dataclass
class BatchNormalization(FeedForwardLayer):
    """Batch norm over the feature axis (reference nn/conf/layers/BatchNormalization.java).

    Works on [B, F] and NHWC [B, H, W, C] inputs (per-channel statistics).
    """

    decay: float = 0.9
    eps: float = 1e-5
    gamma: float = 1.0
    beta: float = 0.0
    lock_gamma_beta: bool = False
    use_global_stats: bool = False  # inference-style stats during training

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            if isinstance(input_type, ConvolutionalInputType):
                self.n_in = input_type.channels
            else:
                self.n_in = input_type.flat_size()
        if self.n_out is None:
            self.n_out = self.n_in

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type


@register
@dataclass
class LayerNormalization(FeedForwardLayer):
    """Layer norm over the trailing feature axis (no 0.4-era reference
    counterpart — added alongside SelfAttentionLayer as the transformer
    building block; normalizes each example independently, so it is
    batch-size- and sequence-parallel-friendly on TPU).

    ``rms``: RMSNorm — no mean is subtracted and there is no bias, only the
    gain. ``unit_offset``: the stored gain is an offset from one (the layer
    multiplies by ``1 + gain`` and the gain starts at zero)."""

    eps: float = 1e-5
    rms: bool = False
    unit_offset: bool = False

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in is None:
            if isinstance(input_type, ConvolutionalInputType):
                self.n_in = input_type.channels
            else:
                self.n_in = input_type.flat_size()
        if self.n_out is None:
            self.n_out = self.n_in

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type


@register
@dataclass
class LocalResponseNormalization(Layer):
    """LRN across channels (reference nn/conf/layers/LocalResponseNormalization.java)."""

    k: float = 2.0
    n: float = 5.0
    alpha: float = 1e-4
    beta: float = 0.75


@dataclass
class BaseRecurrentLayer(FeedForwardLayer):
    def get_output_type(self, input_type: InputType) -> InputType:
        ts = input_type.timesteps if isinstance(input_type, RecurrentInputType) else None
        return InputType.recurrent(self.n_out, ts)


@register
@dataclass
class GravesLSTM(BaseRecurrentLayer):
    """LSTM with peephole connections, per Graves (2013) — the reference's
    flagship RNN (nn/conf/layers/GravesLSTM.java; impl LSTMHelpers.java)."""

    forget_gate_bias_init: float = 1.0


@register
@dataclass
class LSTM(BaseRecurrentLayer):
    """Standard (non-peephole) LSTM."""

    forget_gate_bias_init: float = 1.0


@register
@dataclass
class GravesBidirectionalLSTM(BaseRecurrentLayer):
    """Bidirectional Graves LSTM (reference GravesBidirectionalLSTM.java)."""

    forget_gate_bias_init: float = 1.0


@register
@dataclass
class GRU(BaseRecurrentLayer):
    """Gated recurrent unit (reference nn/conf/layers/GRU.java)."""


@register
@dataclass
class Mamba2Layer(BaseRecurrentLayer):
    """Mamba-2 selective state-space mixer (Dao & Gu 2024, "Transformers are
    SSMs") — see nn/layers/mamba2.py. ``n_heads`` heads of ``head_dim``
    channels (the inner width is their product, whatever ``n_in``), each
    with a ``[head_dim, state_size]`` float32 state; the input-dependent
    ``B`` and ``C`` come in ``n_groups`` groups (head h reads group
    ``h // (n_heads / n_groups)``), behind a causal depthwise conv of
    ``conv_kernel`` taps over the ``inner + 2 n_groups state_size`` conv
    channels. Output is gated (silu) and then RMS-normed over ``n_groups``
    groups of the inner width. No projection has a bias; the conv has one.
    ``chunk_size`` is the chunk of the SSD form a sequence is computed in.
    The four sizes are the model's and have to be given."""

    n_heads: Optional[int] = None
    head_dim: Optional[int] = None
    state_size: Optional[int] = None
    n_groups: Optional[int] = None
    conv_kernel: int = 4
    chunk_size: int = 128
    eps: float = 1e-5

    def __post_init__(self):
        _require(self, "n_heads", "head_dim", "state_size", "n_groups")
        if self.n_heads % self.n_groups:
            raise ValueError(f"n_groups={self.n_groups} must divide "
                             f"n_heads={self.n_heads}")

    def set_n_in(self, input_type: InputType) -> None:
        super().set_n_in(input_type)
        if self.n_out is None:
            self.n_out = self.n_in


@register
@dataclass
class EmbeddingLayer(FeedForwardLayer):
    """Index -> dense vector lookup (reference nn/conf/layers/EmbeddingLayer.java).
    Input: [batch] or [batch, 1] integer indices (or one-hot [batch, n_in])."""

    has_bias: bool = True


@register
@dataclass
class ActivationLayer(Layer):
    """Parameterless activation (reference nn/conf/layers/ActivationLayer.java)."""


@register
@dataclass
class DropoutLayer(Layer):
    """Standalone dropout layer."""


@register
@dataclass
class GlobalPoolingLayer(Layer):
    """Pool over time (RNN) or space (CNN): max|avg|sum|pnorm."""

    pooling_type: str = "max"

    def get_output_type(self, input_type: InputType) -> InputType:
        if isinstance(input_type, RecurrentInputType):
            return InputType.feed_forward(input_type.size)
        if isinstance(input_type, ConvolutionalInputType):
            return InputType.feed_forward(input_type.channels)
        return input_type


@register
@dataclass
class SelfAttentionLayer(FeedForwardLayer):
    """Multi-head self-attention (no reference counterpart; long-context
    capability — see nn/layers/attention.py). ``n_out`` is the model width
    the output projection returns to. A head is ``head_dim`` wide:
    ``n_out / n_heads`` where none is given (``n_heads`` must then divide
    ``n_out``), the model's own where its heads are not the hidden size's
    share (q projects to ``n_heads * head_dim``, o from it)."""

    n_heads: int = 4
    head_dim: Optional[int] = None
    causal: bool = False
    # KV-cache capacity for stateful streaming inference (rnn_time_step);
    # decoding past this many positions is unsupported
    max_cache_len: int = 1024
    # rotary position embeddings (RoPE): inject absolute position by
    # rotating q/k per head-dim pair — no parameters, exact under the KV
    # cache, the standard long-context encoding
    rope: bool = False
    rope_base: float = 10000.0
    # grouped-query attention: K/V projected to this many heads (must
    # divide n_heads); shrinks the KV projections and the decode cache by
    # n_heads/n_kv_heads. None = multi-head (n_kv_heads == n_heads)
    n_kv_heads: Optional[int] = None

    def get_output_type(self, input_type: InputType) -> InputType:
        ts = input_type.timesteps if isinstance(input_type, RecurrentInputType) else None
        return InputType.recurrent(self.n_out, ts)


@register
@dataclass
class EvaAttentionLayer(SelfAttentionLayer):
    """EVA chunked linearized attention (Zheng et al. 2023,
    arXiv:2302.04542) — see nn/layers/attention.py. A query attends exactly
    over its own window of ``window_size`` positions and, in the same
    softmax, over one summary key/value per completed chunk of
    ``chunk_size`` positions of every earlier window. Causal, no biases;
    ``window_size`` must be a multiple of ``chunk_size``."""

    causal: bool = True
    window_size: int = 2048
    chunk_size: int = 16


@register
@dataclass
class LatentAttentionLayer(SelfAttentionLayer):
    """Multi-head latent attention (MLA, the DeepSeek-V2/V3 family) — see
    nn/layers/attention.py. Queries and keys/values go through low-rank
    projections with an RMSNorm each; a head's query and key are a "nope"
    part (``qk_nope_head_dim``, rebuilt from the latent) beside a "rope"
    part (``qk_rope_head_dim``; the key's is ONE rotated row shared by all
    heads). What is cached a position is the normed latent and that rotated
    key: ``kv_lora_rank + qk_rope_head_dim`` values, whatever ``n_heads``.
    Causal, no biases; ``n_out`` is the model width the output projection
    returns to. ``yarn_factor`` > 1 turns on YaRN (blended frequencies over
    ``yarn_original_max`` positions, and the ``mscale`` pair). The five
    widths are the model's and have to be given."""

    causal: bool = True
    rope: bool = True
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    eps: float = 1e-6
    yarn_factor: float = 1.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    def __post_init__(self):
        _require(self, "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                 "qk_rope_head_dim", "v_head_dim")


@register
@dataclass
class RoutedExpertsLayer(FeedForwardLayer):
    """One device's share of a routed mixture of experts — see
    nn/layers/experts.py. The router scores all ``n_experts`` and picks
    ``top_k`` a token; this layer holds experts ``held = (first, count)``
    and returns the part of the result they give (``n_out == n_in``). No
    capacity factor: no token is dropped. ``scoring``: "sigmoid" over the
    router's outputs (the one built); ``norm_topk`` divides the chosen
    scores by their sum (over all chosen, held or not); ``scale`` multiplies
    the gates. ``selection_bias`` adds a learned bias a router output to
    the scores for the CHOICE alone (the gates weigh by the scores).
    ``gated`` experts are three matrices (``act(x Wg) * x Wu) Wd``, SwiGLU
    at the default ``expert_activation`` "swish"); ``gated=False`` is the
    plain two-matrix expert ``act(x Wu) Wd``. ``held=None`` holds every
    expert. ``n_experts``, ``top_k`` and ``width`` (an expert's hidden
    width) are the model's and have to be given."""

    n_experts: Optional[int] = None
    held: Optional[Tuple[int, int]] = None
    top_k: Optional[int] = None
    scoring: str = "sigmoid"
    norm_topk: bool = True
    scale: float = 1.0
    width: Optional[int] = None
    gated: bool = True
    expert_activation: str = "swish"
    selection_bias: bool = False

    def __post_init__(self):
        _require(self, "n_experts", "top_k", "width")
        if self.held is not None:  # a JSON round trip hands back a list
            self.held = (int(self.held[0]), int(self.held[1]))

    def set_n_in(self, input_type: InputType) -> None:
        super().set_n_in(input_type)
        if self.n_out is None:
            self.n_out = self.n_in


@dataclass
class BasePretrainNetwork(FeedForwardLayer):
    loss: str = "reconstruction_crossentropy"

    def is_pretrain_layer(self) -> bool:
        return True


@register
@dataclass
class RBM(BasePretrainNetwork):
    """Restricted Boltzmann machine trained with CD-k
    (reference nn/conf/layers/RBM.java; impl nn/layers/feedforward/rbm/RBM.java:101
    `contrastiveDivergence`)."""

    hidden_unit: str = "binary"  # binary | gaussian | rectified | softmax
    visible_unit: str = "binary"  # binary | gaussian | linear | softmax
    k: int = 1
    sparsity: float = 0.0


@register
@dataclass
class AutoEncoder(BasePretrainNetwork):
    """Denoising autoencoder (reference nn/conf/layers/AutoEncoder.java)."""

    corruption_level: float = 0.3
    sparsity: float = 0.0


def _conv_out_hw(h: int, w: int, kernel, stride, padding, mode: str, dilation) -> Tuple[int, int]:
    kh, kw = kernel
    sh, sw = stride
    ph, pw = padding
    dh, dw = dilation
    ekh = (kh - 1) * dh + 1
    ekw = (kw - 1) * dw + 1
    if mode == "same":
        return ((h + sh - 1) // sh, (w + sw - 1) // sw)
    oh = (h + 2 * ph - ekh) // sh + 1
    ow = (w + 2 * pw - ekw) // sw + 1
    if oh <= 0 or ow <= 0:
        raise ValueError(f"Invalid conv geometry: input {h}x{w}, kernel {kernel}, "
                         f"stride {stride}, padding {padding}")
    return (oh, ow)
