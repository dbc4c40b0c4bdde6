"""Flagship Transformer LM — the multi-chip tpu-native training path.

The reference's NLP coverage is a single-GPU Transformer example
(``examples/nlp/hetu_transformer.py``, unfused BatchMatMul attention). This
module goes well beyond reference parity, because long-context and
distributed are first-class here:

- **dp**: batch sharded over the ``dp`` mesh axis; GSPMD inserts the gradient
  all-reduce over ICI.
- **tp**: Megatron-style sharding — qkv/mlp-in column-parallel, out/mlp-out
  row-parallel over ``tp``; attention heads sharded over ``tp``.
- **sp**: sequence dimension sharded over ``sp``; k/v are gathered for
  attention (Ulysses-style; a Pallas ring-attention path lives in
  ``hetu_tpu/ops/pallas``).
- **moe**: dropless top-k routing — picks sorted by expert, one grouped
  matmul a projection (``_moe_mlp``). Under an ``ep > 1`` mesh the older
  top-1 capacity form stays: experts sharded over ``ep``, token
  dispatch/combine become all-to-alls.
- **pp**: see ``hetu_tpu/parallel/pipeline.py`` (explicit ppermute GPipe).

Params are f32, compute in bf16 (MXU native), losses/reductions f32.
Per-layer params are stacked on a leading L axis and the blocks run under
``lax.scan`` with ``jax.checkpoint`` — one compiled block, L iterations,
activation memory traded for recompute.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import math
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import grouped_matmul as gmm_kernel
from ..kernels import registry as kernel_registry
from ..kernels import rope as rope_kernel
from ..kernels import ssd as ssd_kernel
from ..telemetry.tracing import (REMAT_ATTN_K, REMAT_ATTN_O, REMAT_ATTN_Q,
                                 REMAT_ATTN_V, REMAT_CANDIDATES,
                                 REMAT_DSA_GRADS, REMAT_DSA_MASK,
                                 REMAT_MLA_LATENT,
                                 REMAT_NORM1_IN, REMAT_NORM2_IN, REMAT_X1,
                                 REMAT_X2,
                                 SCOPE_ATTN_GATE, SCOPE_ATTN_ROPE,
                                 SCOPE_BLK_ATTN, SCOPE_BLK_MLP_DOWN,
                                 SCOPE_BLK_MLP_UP, SCOPE_BLK_NORM,
                                 SCOPE_BLK_QKV, SCOPE_BLK_WO, SCOPE_DSA_LOSS,
                                 SCOPE_DSA_PROJ, SCOPE_DSA_SELECT,
                                 SCOPE_EMBED, SCOPE_EXIT, SCOPE_FWD,
                                 SCOPE_GDN_CONV, SCOPE_GDN_GATE,
                                 SCOPE_GDN_PROJ, SCOPE_GDN_SCAN,
                                 SCOPE_HEAD, SCOPE_KDA_CONV, SCOPE_KDA_GATE,
                                 SCOPE_KDA_PROJ, SCOPE_KDA_SCAN,
                                 SCOPE_MLA_KV_DOWN, SCOPE_MLA_KV_UP,
                                 SCOPE_MLA_Q, SCOPE_MOE_ACT, SCOPE_MOE_COMBINE,
                                 SCOPE_MOE_DISPATCH, SCOPE_MOE_EXPERTS,
                                 SCOPE_MOE_ROUTE, SCOPE_MOE_ROUTE_EARLY,
                                 SCOPE_MOE_SHARED,
                                 SCOPE_OPT, SCOPE_SCONV_CONV,
                                 SCOPE_SCONV_PROJ, SCOPE_SSD_ENTER,
                                 SCOPE_SSD_INCHUNK, SCOPE_SSD_STATES,
                                 SCOPE_SSM_CONV, SCOPE_SSM_GATE,
                                 SCOPE_SSM_GATE_NORM, SCOPE_SWA_ATTN,
                                 SCOPE_SSM_PROJ, SCOPE_SSM_SCAN, scoped)

_log = logging.getLogger(__name__)

# router z-loss weight (ST-MoE, OLMoE: 1e-3); the balance loss keeps
# ``loss_fn``'s ``aux_weight``
Z_LOSS_WEIGHT = 1e-3
# the indexer's own loss of a learned-sparse-attention layer (``_dsa``): it
# moves the indexer's leaves alone, so the weight is that module's own
# learning rate scale (DeepSeek-V3.2-Exp's sparse training stage: 1)
DSA_LOSS_WEIGHT = 1.0
# entropy bonus on a looped model's exit distribution (Ouro stage I,
# arXiv:2510.25741: 0.1 early, 0.05 later; a uniform prior over exit steps)
EXIT_ENTROPY_WEIGHT = 0.05


# the leaf of an expert layer that no gradient moves: the router's
# selection bias (``Router.bias``); ``adamw_update`` leaves a leaf of this
# name alone and ``make_train_step`` moves it by the rule of
# ``Router.bias_rate``
ROUTER_BIAS = "router_bias"


# the gated MLP forms, down(act(gate(x)) * up(x)): three matrices ``w1``
# (gate), ``w3`` (up), ``w2`` (down), dense or an expert; they differ in
# the activation alone (``_swiglu`` / ``_reglu``)
GATED_MLPS = ("swiglu", "reglu")


class MoEConfigError(ValueError):
    """A MoE setting the chosen layout does not implement: a router's
    form, the share of an expert layer, or a mesh they cannot run on."""


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """The six sizes of a Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060;
    ``_mamba``): ``n_heads`` heads of ``head_dim`` channels, each head a
    (head_dim, d_state) state; B and C are shared by the heads of a group.
    The last two are ``mamba_ssm``'s ``Mamba2`` where HF Granite has neither
    (``models/hf_nemotron_h.py`` sets both); the defaults leave the program
    and the initial weights what they are."""
    n_heads: int = 64
    head_dim: int = 64
    d_state: int = 128
    n_groups: int = 1
    d_conv: int = 4             # width of the causal depthwise convolution
    chunk: int = 256            # positions a chunk of the SSD form
    norm_groups: int = 1        # the gated norm's statistic runs over each
                                # of this many runs of d_inner / norm_groups
                                # channels (``RMSNormGated``'s ``group_size``);
                                # 1 = over all channels
    dt_init: Optional[tuple] = None     # (min, max, floor): a head's initial
                                        # step size log-uniform in [min, max],
                                        # at least floor, and ``dt_bias`` its
                                        # inverse softplus; None = dt_bias 1

    @property
    def d_inner(self):
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self):
        """Channels the convolution runs over: [x | B | C]."""
        return self.d_inner + 2 * self.n_groups * self.d_state


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """The four sizes of latent attention (DeepSeek-V2, arXiv:2405.04434,
    section 2.1; ``_mla``): keys and values come up from ONE latent of
    ``kv_rank`` columns a token; a head's q and k are ``nope_dim`` columns
    without position beside ``rope_dim`` rotary ones, and the rotary key is
    one a token, shared by the heads; a head's v is ``v_dim`` columns. The
    scores are ``qk_dim`` wide and the values ``v_dim``: two head widths."""
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rotate: bool = True         # False (Kimi Linear's ``mla_use_nope``):
                                # NOTHING is rotated; the ``rope_dim`` columns
                                # stay in the product, q's own and the one key
                                # a token every head's, without position

    @property
    def qk_dim(self):
        return self.nope_dim + self.rope_dim


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    """The sizes of a Kimi Delta Attention mixer (Kimi Linear,
    arXiv:2510.26692; ``_kda``, ``models/kda.py``): ``n_heads`` heads of
    ``head_dim`` columns for k AND for v (a head's state is head_dim x
    head_dim), a log-decay a CHANNEL from a low-rank gate of ``head_dim``
    columns, an output gate of the same rank, q, k and v each through its own
    causal depthwise convolution of ``d_conv`` taps."""
    n_heads: int = 32
    head_dim: int = 128
    d_conv: int = 4
    chunk: int = 64             # positions a chunk of the chunked rule: 16 x
                                # a power of two (``kda.SUB``), or fewer
    dt_init: tuple = (1e-3, 1e-1, 1e-4)     # (min, max, floor): a channel's
                                            # initial step log-uniform in [min,
                                            # max], at least floor; ``dt_bias``
                                            # its inverse softplus
                                            # (``SSMConfig.dt_init``'s form)

    @property
    def d_inner(self):
        return self.n_heads * self.head_dim


@dataclasses.dataclass(frozen=True)
class GDNConfig:
    """The sizes of a Gated DeltaNet mixer (Yang et al. 2024,
    arXiv:2412.06464, as Qwen3-Next's ``Qwen3NextGatedDeltaNet`` has it;
    ``_gdn``, ``models/kda.py``): ``n_k_heads`` key heads of ``k_dim`` columns
    under ``n_v_heads`` value heads of ``v_dim`` (key head j serves value
    heads r j .. r j + r - 1, r = n_v_heads / n_k_heads; a value head's state
    is k_dim x v_dim), ONE log-decay a value head and position, [q | k | v]
    through ONE causal depthwise convolution of ``d_conv`` taps, an output
    gate SiLU(z) of the value heads' width after the head norm."""
    n_k_heads: int = 16
    n_v_heads: int = 32
    k_dim: int = 128
    v_dim: int = 128
    d_conv: int = 4
    chunk: int = 64             # positions a chunk of the chunked rule, a
                                # power of two

    @property
    def qk_inner(self):
        return self.n_k_heads * self.k_dim

    @property
    def v_inner(self):
        return self.n_v_heads * self.v_dim

    @property
    def conv_dim(self):
        """Channels the convolution runs over: [q | k | v]."""
        return 2 * self.qk_inner + self.v_inner


@dataclasses.dataclass(frozen=True)
class DSAConfig:
    """The three sizes of a learned sparse attention's indexer (DeepSeek-
    V3.2-Exp's lightning indexer, arXiv:2512.02556; ``_dsa``): ``n_heads``
    index heads of ``head_dim`` columns score every key against ONE index key
    a token, and a query attends to the ``top_k`` keys of largest score."""
    n_heads: int = 16
    head_dim: int = 64
    top_k: int = 2048


@dataclasses.dataclass(frozen=True)
class WindowConfig:
    """The sizes of a "window" layer, where they are its own and not the
    model's (``_window``): grouped-query attention in which query t keeps
    the ``window`` keys t - window < s <= t, with ``n_heads`` query heads
    (on the model's ``kv_heads``, at its ``head_dim``) and rotate-half RoPE
    at ``rope_theta`` on ALL of a head's columns, no frequency scaling,
    whatever ``rope``, ``rope_dim`` and ``rope_yarn`` say of the "attention"
    layers: the rotary form is the KIND's, and a stack may rotate in its
    window layers alone (``cfg.rope`` false: NoPE attention layers beside
    them, SmallThinker's)."""
    window: int = 512
    n_heads: int = 64
    rope_theta: float = 10000.0


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN's frequency scaling (Peng et al. 2023, arXiv:2309.00071;
    ``transformers`` ``_compute_yarn_parameters``; ``kernels/rope.py``:
    ``yarn_inv_freq``): of a rotary table's frequencies those that turn
    fewer than ``beta_slow`` times in ``original_max_len`` positions are
    divided by ``factor``, those that turn more than ``beta_fast`` times
    stay, a linear ramp between; cos and sin are multiplied by
    ``attention_factor``."""
    factor: float = 1.0
    original_max_len: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """Granite's four scalars (``models/hf_granite.py``); the defaults are
    neutral and leave the program what it is without them."""
    embedding: float = 1.0      # on the token embeddings
    residual: float = 1.0       # on each sublayer's output, before its add
    attention: Optional[float] = None   # the softmax scale; None =
                                        # 1/sqrt(head_dim)
    logits: float = 1.0         # the logits are DIVIDED by it


@dataclasses.dataclass(frozen=True)
class Router:
    """The form of an expert layer's router (``_route``) and the chip's share
    of the layer (``_moe_mlp``); the defaults are OLMoE's softmax router over
    experts that are all held here, and leave the program what it is."""
    score: str = "softmax"      # "softmax" over the experts | "sigmoid" an
                                # expert, both float32
    bias: bool = False          # a per-expert bias (leaf ``router_bias``)
                                # added to the scores for the SELECTION
                                # only; no gradient moves it: ``bias_rate``
    normalize: bool = False     # the picks' weights over (their sum +
                                # ``normalize_eps``)
    normalize_eps: float = 1e-6     # LFM2's; DeepSeek-V3's is 1e-20
    scale: float = 1.0          # on the picks' weights, after that
    aux_losses: bool = True     # the balance and z losses (``loss_fn``);
                                # False: ``aux`` is zeros
    loss_weights: Optional[tuple] = None    # (balance, z): the two losses'
                                            # weights in ``loss_fn``; None =
                                            # its caller's ``aux_weight`` and
                                            # ``Z_LOSS_WEIGHT``
    bias_rate: float = 0.0      # u of the auxiliary-loss-free rule (Wang et
                                # al. 2024, arXiv:2408.15664), applied by
                                # ``make_train_step`` after a step: b_e += u
                                # sign(mean(c) - c_e), c the picks each
                                # expert took in the step's batch
    # the share: the router has ``width`` outputs and picks among all of
    # them; this program holds the weights of experts [first_held,
    # first_held + cfg.n_experts) and computes the picks that land there
    width: int = 0              # 0 = ``cfg.n_experts``: every expert is here
    first_held: int = 0
    input: str = "mlp"          # what the router reads: "mlp" the MLP half's
                                # normed input, as the experts do; "block"
                                # the residual stream as it ENTERS the layer,
                                # before the mixer and its norm (``_block``
                                # routes ahead of the mixer: SmallThinker)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 8
    d_ff: int = 2048
    max_seq_len: int = 1024
    n_experts: int = 0          # 0 = dense MLP; >0 = MoE (experts honour
                                # ``mlp``: gelu with biases, or swiglu):
                                # the experts whose weights are HERE (all
                                # of them unless ``router.width`` says more)
    n_experts_per_tok: int = 1  # picks a token: the k largest router
                                # scores, weighted as ``router`` says
                                # (softmax probabilities UNnormalised by
                                # default; sigmoid scores normalised over
                                # the k picks for LFM2)
    capacity_factor: float = 1.25   # ep > 1 meshes only (``_moe_mlp``)
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16   # compute dtype
    remat: bool = True
    causal: bool = True         # False = bidirectional encoder (BERT)
    # attention implementation: "auto" picks ring when the mesh shards the
    # sequence (sp>1), the fused Pallas kernel on TPU for block-divisible
    # sequences, and the unfused dot-product form otherwise
    attn_impl: str = "auto"     # auto | dot | flash | ring
    # LM loss through the fused Pallas linear+softmax-CE kernel
    # (kernels/fused_ce.py): skips the (B*T, V) logits tensor on the
    # single-program TPU path. "auto" = TPU only; True forces (tests);
    # False = always materialize. Meshes keep the einsum form (GSPMD
    # cannot partition the custom kernel).
    fused_lm_ce: Any = "auto"
    # Canonical-BERT architecture knobs (default = the flagship pre-LN
    # trunk; models/hf_bert.py flips all four to load HuggingFace BERT
    # checkpoints weight-for-weight):
    post_ln: bool = False       # LN after each residual add (original
                                # Transformer/BERT) instead of before the
                                # sublayer; the final lnf is NOT applied by
                                # the trunk in this mode (BERT has no final
                                # LN — callers repurpose lnf as the
                                # embedding LN)
    ln_eps: float = 1e-5        # HF BERT uses 1e-12
    gelu_exact: bool = False    # erf gelu (HF "gelu") vs tanh approximation
    attn_proj_bias: bool = False  # bias terms on the qkv and output
                                  # projections (BERT has them; GPT-style
                                  # flagship configs do not)
    tied_head: bool = False     # LM head shares the token embedding (GPT-2
                                # semantics): no separate "head" param, the
                                # vocab projection is embed itself — halves
                                # embedding memory and keeps fine-tuned
                                # weights exportable as a tied checkpoint
    # Llama-family dialect knobs (models/hf_llama.py flips these to load
    # HF Llama/Mistral-class checkpoints weight-for-weight):
    norm: str = "layernorm"     # "rmsnorm": x·rsqrt(mean(x²)+eps)·scale,
                                # no bias/mean-centering (the *_bias params
                                # exist but are ignored so pytree structure
                                # is dialect-independent)
    rope: bool = False          # rotary position embeddings on q/k (the
                                # cache stores ROTATED keys); replaces the
                                # learned "pos" table. The "attention" (and
                                # "dsa") layers'; a "window" layer rotates
                                # by ``window`` whatever this says
    rope_theta: float = 10000.0
    mlp: str = "gelu"           # "swiglu": down(silu(gate(x))·up(x)) with
                                # an extra w3 (up) weight, no biases used;
                                # "relu2": down(relu(up(x))²), two matrices,
                                # no gate and no bias leaves (Nemotron-H);
                                # "reglu": down(relu(gate(x))·up(x)), the
                                # leaves of "swiglu" (SmallThinker)
    n_kv_heads: int = 0         # grouped-query attention: 0 = n_heads
                                # (MHA); otherwise k/v project to n_kv
                                # heads and broadcast to the q heads
    use_pos_emb: bool = True    # False: no learned position table (rope
                                # carries positions)
    qk_norm: Any = False        # True (OLMoE): RMSNorm (``q_norm`` /
                                # ``k_norm`` scales, ``ln_eps``) over the
                                # whole q and k projections, before the
                                # head split; "head" (LFM2): over each
                                # head's ``head_dim`` channels, one scale
                                # of ``head_dim`` shared by the heads
    # Looped-LM dialect knobs (models/hf_ouro.py sets both):
    n_loops: int = 1            # > 1: the block stack runs this many times
                                # over ONE set of weights, the final norm
                                # after each pass; every pass's state is an
                                # exit that ``loss_fn`` weights by a learned
                                # gate (``exit_gate_w``/``exit_gate_b``)
    sandwich_norm: bool = False  # a norm after each sublayer too, before
                                 # the residual add (``ln1_post_*`` /
                                 # ``ln2_post_*``); pre-LN only
    # Hybrid stacks (models/hf_granite.py sets all three):
    layer_types: tuple = ()     # a mixer of ``_KINDS`` a layer ("attention",
                                # "mamba", "conv", "mla", "dsa", "window", "kda",
                                # "gdn";
                                # "mlp" under ``single_sublayer``);
                                # () =
                                # ``n_layers``
                                # of attention. ``encode`` scans each run of
                                # one kind; ``params["blocks"]`` is the
                                # stacked dict of a stack with one run,
                                # else a tuple of them, one a run
                                # (``layer_runs``)
    ssm: Optional[SSMConfig] = None     # the "mamba" layers' sizes
    multipliers: Multipliers = Multipliers()
    # Expert models with leading dense layers and a router of another form
    # (models/hf_lfm2.py sets all five):
    n_dense_layers: int = 0     # of an expert model (``n_experts`` > 0):
                                # the first layers' MLP half is the dense
                                # MLP of width ``d_ff``; their kind is the
                                # mixer's name + ``DENSE`` (``layer_kinds``)
    d_ff_expert: int = 0        # an expert's width; 0 = ``d_ff``
    conv_width: int = 3         # taps of a "conv" layer's causal depthwise
                                # convolution (LFM2 ``conv_L_cache``)
    router: Router = Router()
    # Latent attention and a shared expert (models/hf_deepseek_v3.py sets
    # both):
    mla: Optional[MLAConfig] = None     # the "mla" layers' sizes
    kda: Optional[KDAConfig] = None     # the "kda" layers' sizes
                                        # (models/hf_kimi_linear.py)
    # Gated DeltaNet beside gated attention, zero-centred norms and a gated
    # shared expert (models/hf_qwen3_next.py sets all four):
    gdn: Optional[GDNConfig] = None     # the "gdn" layers' sizes
    norm_offset: bool = False   # zero-centred RMSNorm: the stored weight is
                                # w of a scale 1 + w, initialised at ZERO
                                # (AdamW decays w towards a scale of 1): every
                                # norm of the stream (``_norm``) and the
                                # per-head q/k norms (``qk_norm`` "head")
    shared_gate: bool = False   # the shared expert's output times sigmoid(h
                                # w_sg), ONE gate logit a token (leaf ``wsg``,
                                # (D, 1))
    d_ff_shared: int = 0        # > 0: an expert layer has an always-on
                                # branch too, ONE SwiGLU MLP of this width on
                                # every token beside the routed picks
                                # (``ws1`` / ``ws3`` / ``ws2``; DeepSeek's
                                # shared experts, side by side); a share
                                # (``router.width``) computes it whole
    # A head width of its own and learned sparse attention
    # (models/hf_keye.py sets both):
    d_head: int = 0             # columns a head of q, k and v; 0 = ``d_model
                                # // n_heads`` (``head_dim`` is what is read)
    dsa: Optional[DSAConfig] = None     # the "dsa" layers' indexer
    # Window and full attention in one stack, each with a head count and a
    # rotary form of its own, and a gate on attention's output
    # (models/hf_laguna.py sets all four):
    window: Optional[WindowConfig] = None   # the "window" layers' sizes;
                                            # "attention" layers keep
                                            # ``n_heads`` / ``rope_theta``
    rope_dim: int = 0           # columns of a head that turn in an
                                # "attention" layer, its first ones; the
                                # others pass (HF ``partial_rotary_factor``);
                                # 0 = ``head_dim``
    rope_yarn: Optional[YarnConfig] = None  # the "attention" layers' rotary
                                            # table scaled by YaRN
    attn_gate: Any = False      # a sigmoid gate from the layer's normed
                                # input on attention's output, before ``wo``:
                                # True (Laguna) one a HEAD, leaf ``wg`` (D,
                                # heads); "column" (Qwen3-Next) one a COLUMN,
                                # ``wg`` (D, heads * head_dim)
    # Layers of ONE sublayer (models/hf_nemotron_h.py sets it):
    single_sublayer: bool = False   # every layer is x + f(norm(x)) with ONE
                                    # norm: a mixer of ``layer_types`` WITHOUT
                                    # an MLP half (kind: its name + ``ALONE``),
                                    # or, named "mlp", the MLP half WITHOUT a
                                    # mixer (experts where ``n_experts``)

    def __post_init__(self):
        if self.layer_types:
            unknown = set(self.layer_types) - set(_KINDS)
            if unknown or len(self.layer_types) != self.n_layers or (
                    "mamba" in self.layer_types and (
                        self.ssm is None or self.post_ln)):
                raise ValueError(
                    f"layer_types={self.layer_types}: {self.n_layers} kinds "
                    f"of {sorted(_KINDS)} (mamba: pre-LN, with `ssm` sizes)")
        if "kda" in self.layer_types and (
                self.kda is None or self.post_ln or not self.causal
                or self.kda.chunk & (self.kda.chunk - 1)):
            raise ValueError(
                f"layer_types={self.layer_types}: a kda layer takes `kda` "
                "sizes (a chunk that is a power of two), pre-LN and a causal "
                "model (the state runs forward in time)")
        if "gdn" in self.layer_types and (
                self.gdn is None or self.post_ln or not self.causal
                or self.gdn.chunk & (self.gdn.chunk - 1)
                or self.gdn.n_v_heads % self.gdn.n_k_heads):
            raise ValueError(
                f"layer_types={self.layer_types}, gdn={self.gdn}: a gdn layer "
                "takes `gdn` sizes (a chunk that is a power of two, value "
                "heads in whole groups a key head), pre-LN and a causal "
                "model (the state runs forward in time)")
        if "mla" in self.layer_types and (
                self.mla is None or self.post_ln or self.attn_proj_bias
                or self.qk_norm or self.n_kv_heads
                or self.multipliers.attention is not None):
            raise ValueError(
                f"layer_types={self.layer_types}: an mla layer takes `mla` "
                "sizes, pre-LN, and no projection bias, QK-norm, grouped "
                "heads or attention multiplier (beside mamba, conv or kda "
                "layers as beside its own kind)")
        if "dsa" in self.layer_types and (
                self.dsa is None or self.post_ln or not self.causal
                or not self.rope):
            raise ValueError(
                f"layer_types={self.layer_types}: a dsa layer takes `dsa` "
                "sizes, pre-LN, causal attention and RoPE (the indexer "
                "rotates its queries and keys too)")
        if "window" in self.layer_types and (
                self.window is None or self.post_ln or not self.causal
                or self.window.window < 1
                or self.window.n_heads % self.kv_heads):
            raise ValueError(
                f"layer_types={self.layer_types}: a window layer takes "
                "`window` sizes (a positive window, a head count that the "
                "k/v heads divide), pre-LN and causal attention")
        if (self.rope_dim or self.rope_yarn) and not self.rope:
            raise ValueError(
                f"rope_dim={self.rope_dim}, rope_yarn={self.rope_yarn}: the "
                "rotary form of \"attention\" layers that rotate (`rope`)")
        if (self.rope_dim or self.rope_yarn or self.attn_gate) and (
                {"mla", "dsa", "kda"} & set(self.layer_types)
                or self.rope_dim % 2 or self.rope_dim > self.head_dim
                or self.attn_gate not in (False, True, "column")):
            raise ValueError(
                f"rope_dim={self.rope_dim}, rope_yarn={self.rope_yarn}, "
                f"attn_gate={self.attn_gate}: the \"attention\" and "
                "\"window\" layers' own, of a stack whose other layers are "
                "of those kinds or mamba, conv or gdn (mla, dsa and kda "
                "layers have neither, and no stack mixes them with a rotary "
                "width or a gate); rope_dim an even count of a head's "
                "columns; attn_gate True (a head) or 'column'")
        if self.norm_offset and (self.norm != "rmsnorm"
                                 or self.qk_norm is True):
            raise ValueError(
                f"norm_offset with norm={self.norm!r}, qk_norm="
                f"{self.qk_norm!r}: the zero-centred form is RMSNorm's, of "
                "the stream's norms and the per-head q/k norms")
        if self.shared_gate and not self.d_ff_shared:
            raise MoEConfigError(
                "shared_gate: the gate of a shared expert (`d_ff_shared`)")
        if ("mlp" in self.layer_types) > self.single_sublayer or (
                self.single_sublayer and (
                    self.post_ln or self.sandwich_norm or self.n_dense_layers
                    or self.n_loops > 1)):
            raise ValueError(
                f"layer_types={self.layer_types}, single_sublayer="
                f"{self.single_sublayer}: an \"mlp\" layer is a layer of a "
                "single-sublayer stack, which is pre-LN without sandwich "
                "norms, leading dense layers or loops")
        if self.mlp not in ("gelu", "relu2") + GATED_MLPS:
            raise ValueError(f"mlp={self.mlp!r}: 'gelu', 'relu2' or one of "
                             f"{GATED_MLPS}")
        if self.d_ff_shared and not (self.n_experts
                                     and self.mlp in ("swiglu", "relu2")):
            raise MoEConfigError(
                f"d_ff_shared={self.d_ff_shared}: the shared expert of an "
                "expert model (`n_experts` > 0) with SwiGLU or relu2 experts")
        r = self.router
        if self.n_experts and not (
                1 <= self.n_experts_per_tok <= (r.width or self.n_experts)):
            raise MoEConfigError(
                f"n_experts_per_tok={self.n_experts_per_tok} of "
                f"{r.width or self.n_experts} routed experts")
        if r.width and not (
                0 <= r.first_held <= r.width - self.n_experts):
            raise MoEConfigError(
                f"the share: experts [{r.first_held}, "
                f"{r.first_held + self.n_experts}) held of a router of "
                f"width {r.width}")
        if r.score not in ("softmax", "sigmoid") or (
                r.bias_rate and not r.bias) or r.input not in (
                    "mlp", "block") or (
                        r.input == "block" and (self.post_ln
                                                or self.single_sublayer)):
            raise MoEConfigError(
                f"{r}: score is 'softmax' or 'sigmoid'; bias_rate moves a "
                "bias that is there; input is 'mlp' or 'block' (a pre-LN "
                "layer of two halves: its input, ahead of the mixer)")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm={self.qk_norm!r}: False, True (the "
                             "whole projection) or 'head'")
        if self.n_dense_layers and not (
                self.n_experts and self.n_dense_layers <= self.n_layers):
            raise MoEConfigError(
                f"n_dense_layers={self.n_dense_layers}: the leading dense "
                f"layers of an expert model of {self.n_layers} layers")
        if self.n_loops < 1 or (self.post_ln and (
                self.n_loops > 1 or self.sandwich_norm)):
            raise ValueError(
                f"n_loops={self.n_loops}, sandwich_norm={self.sandwich_norm}"
                f", post_ln={self.post_ln}: loops and sandwich norms are "
                "pre-LN, n_loops >= 1")

    @property
    def kv_heads(self):
        n = self.n_kv_heads or self.n_heads
        assert self.n_heads % n == 0
        return n

    @property
    def head_dim(self):
        if self.d_head:
            return self.d_head
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


# ---------------------------------------------------------------------------
# parameter init + sharding rules
# ---------------------------------------------------------------------------

def _init_normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def init_trunk_params(rng, cfg: TransformerConfig):
    """The block stack + final norm ONLY — for trunk-reusing families
    (ViT) that would otherwise materialize a dead embedding/pos/head just
    to throw them away. ``init_params`` shares the same key schedule, so a
    trunk initialized here is bit-identical to one sliced out of it."""
    return _init_trunk(jax.random.split(rng, 12), cfg)


# the suffix of a kind whose MLP half is the dense MLP in an expert model
DENSE = "+dense"
# the suffix of a kind WITHOUT an MLP half (``cfg.single_sublayer``)
ALONE = "+alone"


def layer_kinds(cfg: TransformerConfig):
    """A kind a layer, naming its mixer AND its MLP half: the mixer's name
    of ``_KINDS`` where the MLP half is the model's own (experts where
    ``cfg.n_experts``, else dense), the name + ``DENSE`` on the
    ``cfg.n_dense_layers`` leading layers of an expert model. In a stack of
    single sublayers (``cfg.single_sublayer``) a mixer's name + ``ALONE``
    (no MLP half), and "mlp" as it is (the MLP half, no mixer)."""
    mixers = cfg.layer_types or ("attention",) * cfg.n_layers
    if cfg.single_sublayer:
        return tuple(m if m == "mlp" else m + ALONE for m in mixers)
    return tuple(m + DENSE if i < cfg.n_dense_layers else m
                 for i, m in enumerate(mixers))


def mixer_of(kind):
    """A kind's mixer, a name of ``_KINDS`` ("mlp": none)."""
    return kind.removesuffix(DENSE).removesuffix(ALONE)


def has_mlp(kind):
    """Whether a layer of ``kind`` has an MLP half."""
    return not kind.endswith(ALONE)


def experts_of(cfg: TransformerConfig, kind):
    """Experts held by a layer of ``kind``; 0 = its MLP half is dense, or
    it has none."""
    return 0 if kind.endswith((DENSE, ALONE)) else cfg.n_experts


def layer_runs(cfg: TransformerConfig):
    """The stack as runs of one kind, in order: ((kind, layers), ...)."""
    runs = []
    for kind in layer_kinds(cfg):
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return tuple((kind, n) for kind, n in runs)


def run_layers(cfg: TransformerConfig):
    """The runs with the stack's layer indices of each: [(kind, [i, ...])]
    in order."""
    out, first = [], 0
    for kind, n in layer_runs(cfg):
        out.append((kind, list(range(first, first + n))))
        first += n
    return out


def run_blocks(cfg: TransformerConfig, blocks):
    """``params["blocks"]`` (or a tree shaped like it) as a tuple with one
    stacked entry a run of ``layer_runs``."""
    return (blocks,) if len(layer_runs(cfg)) == 1 else tuple(blocks)


def blocks_of_runs(runs):
    """The inverse of ``run_blocks``: one stacked entry a run ->
    ``params["blocks"]`` (the entry itself where the stack is one run)."""
    return runs[0] if len(runs) == 1 else tuple(runs)


def _init_norm_scale(cfg: TransformerConfig, shape):
    """A norm's stored weight as initialised: the scale 1, or under
    ``cfg.norm_offset`` w = 0 of a scale 1 + w."""
    return (jnp.zeros if cfg.norm_offset else jnp.ones)(shape, jnp.float32)


def _init_attention(ks, cfg: TransformerConfig, n):
    D = cfg.d_model
    qkv_width = (cfg.n_heads + 2 * cfg.kv_heads) * cfg.head_dim
    p = {"wqkv": _init_normal(ks[0], (n, D, qkv_width), 0.02),
         "wo": _init_normal(ks[1], (n, cfg.n_heads * cfg.head_dim, D),
                            0.02 / np.sqrt(2 * cfg.n_layers))}
    if cfg.attn_proj_bias:
        p["bqkv"] = jnp.zeros((n, qkv_width), jnp.float32)
        p["bo"] = jnp.zeros((n, D), jnp.float32)
    if cfg.qk_norm == "head":
        p["q_norm"] = _init_norm_scale(cfg, (n, cfg.head_dim))
        p["k_norm"] = _init_norm_scale(cfg, (n, cfg.head_dim))
    elif cfg.qk_norm:
        p["q_norm"] = jnp.ones((n, cfg.n_heads * cfg.head_dim), jnp.float32)
        p["k_norm"] = jnp.ones((n, cfg.kv_heads * cfg.head_dim), jnp.float32)
    if cfg.attn_gate:
        p["wg"] = _init_normal(
            jax.random.fold_in(ks[11], 3),
            (n, D, cfg.n_heads * (cfg.head_dim if cfg.attn_gate == "column"
                                  else 1)), 0.02)
    return p


def _attention_specs(cfg: TransformerConfig):
    p = {"wqkv": P(None, None, "tp"), "wo": P(None, "tp", None)}
    if cfg.attn_gate:
        p["wg"] = P(None, None, "tp")       # a head's gate(s) with the head
    if cfg.attn_proj_bias:
        p["bqkv"], p["bo"] = P(None, "tp"), P(None, None)
    if cfg.qk_norm:
        # a head's scale is every head's: replicated
        p["q_norm"] = p["k_norm"] = (P(None, None) if cfg.qk_norm == "head"
                                     else P(None, "tp"))
    return p


@functools.lru_cache(maxsize=None)
def _window_view(cfg: TransformerConfig):
    """The config a "window" layer's attention reads: the model's, with the
    window kind's own head count and rotary form (``WindowConfig``: it
    rotates whether or not the "attention" layers do) where
    ``_init_attention``, ``_split_heads`` and ``_flash`` read the
    "attention" layers'."""
    w = cfg.window
    return dataclasses.replace(cfg, n_heads=w.n_heads, rope=True,
                               rope_theta=w.rope_theta, rope_dim=0,
                               rope_yarn=None)


def _mixer_view(cfg: TransformerConfig, mixer):
    """The config the attention of mixer kind ``mixer`` reads."""
    return _window_view(cfg) if mixer == "window" else cfg


def _init_window(ks, cfg: TransformerConfig, n):
    return _init_attention(ks, _window_view(cfg), n)


def _window_specs(cfg: TransformerConfig):
    return _attention_specs(_window_view(cfg))


def _init_mamba(ks, cfg: TransformerConfig, n):
    """HF ``GraniteMoeHybridPreTrainedModel._init_weights``: A = 1..H a
    head, dt_bias = D = 1, the gated norm's scale 1, the convolution's bias
    0, normal(0.02) elsewhere. Under ``SSMConfig.dt_init`` dt_bias is
    ``mamba_ssm``'s ``Mamba2``'s instead: the inverse softplus of a step size
    drawn log-uniform in [min, max] a head and layer, at least floor."""
    m, D = cfg.ssm, cfg.d_model
    ones = lambda *shape: jnp.ones((n,) + shape, jnp.float32)
    dt_bias = ones(m.n_heads)
    if m.dt_init is not None:
        lo, hi, floor = m.dt_init
        u = jax.random.uniform(jax.random.fold_in(ks[11], 4), (n, m.n_heads))
        dt = jnp.maximum(jnp.exp(u * np.log(hi / lo) + np.log(lo)), floor)
        dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    return {
        "w_in": _init_normal(
            ks[0], (n, D, m.d_inner + m.conv_dim + m.n_heads), 0.02),
        "conv_w": _init_normal(ks[11], (n, m.d_conv, m.conv_dim), 0.02),
        "conv_b": jnp.zeros((n, m.conv_dim), jnp.float32),
        "dt_bias": dt_bias,
        "A_log": jnp.tile(jnp.log(jnp.arange(1, m.n_heads + 1,
                                             dtype=jnp.float32)), (n, 1)),
        "D": ones(m.n_heads),
        "ssm_norm": ones(m.d_inner),
        "w_out": _init_normal(ks[1], (n, m.d_inner, D), 0.02)}


def _mamba_specs(cfg: TransformerConfig):
    """Replicated: the in-projection's columns are [z | x | B | C | dt] and
    B, C are shared by the heads, so no column cut is a head cut (a ``tp``
    axis computes the mixer on every device)."""
    return {name: P() for name in ("w_in", "conv_w", "conv_b", "dt_bias",
                                   "A_log", "D", "ssm_norm", "w_out")}


def _init_short_conv(ks, cfg: TransformerConfig, n):
    """HF ``PreTrainedModel._init_weights``: normal(0.02) for the two
    Linears and the Conv1d alike; no bias anywhere."""
    D = cfg.d_model
    return {"w_in": _init_normal(ks[0], (n, D, 3 * D), 0.02),
            "conv_w": _init_normal(ks[11], (n, cfg.conv_width, D), 0.02),
            "w_out": _init_normal(ks[1], (n, D, D), 0.02)}


def _short_conv_specs(cfg: TransformerConfig):
    """Replicated, as the mamba mixer: the columns [B | C | x] meet
    channel by channel, so a column cut would have to cut all three alike."""
    return {name: P() for name in ("w_in", "conv_w", "w_out")}


def _init_mla(ks, cfg: TransformerConfig, n):
    """HF ``PreTrainedModel._init_weights`` (``deepseek_v3``): normal(0.02)
    for every Linear, the latent's norm 1; `wo` takes the trunk's depth
    scaling as every mixer's output projection here does. ``wkv_b``'s
    columns are [every head's k_nope | every head's v] (``_mla``)."""
    m, D, nh = cfg.mla, cfg.d_model, cfg.n_heads
    return {
        "wq": _init_normal(ks[0], (n, D, nh * m.qk_dim), 0.02),
        "wkv_a": _init_normal(ks[11], (n, D, m.kv_rank + m.rope_dim), 0.02),
        "kv_norm": jnp.ones((n, m.kv_rank), jnp.float32),
        "wkv_b": _init_normal(jax.random.fold_in(ks[11], 1),
                              (n, m.kv_rank, nh * (m.nope_dim + m.v_dim)),
                              0.02),
        "wo": _init_normal(ks[1], (n, nh * m.v_dim, D),
                           0.02 / np.sqrt(2 * cfg.n_layers))}


def _mla_specs(cfg: TransformerConfig):
    """Replicated: the latent and the rotary key are every head's, and
    ``wkv_b``'s columns are two runs of heads, so no one column cut is a head
    cut of all five (a ``tp`` axis computes the projections on every device;
    the kernels still run a shard of the heads each, ``_flash``)."""
    return {name: P() for name in ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")}


KDA_LEAVES = ("kda_wqkv", "kda_conv", "kda_fa", "kda_fb", "kda_dt_bias",
              "kda_A_log", "kda_wb", "kda_ga", "kda_gb", "kda_norm", "kda_wo")


def _init_kda(ks, cfg: TransformerConfig, n):
    """flash-linear-attention's ``KimiDeltaAttention`` layer: ``A_log`` = log
    U(1, 16) a head; ``dt_bias`` the inverse softplus of a step log-uniform
    in ``dt_init``'s [min, max], at least its floor, a CHANNEL; the
    convolutions' taps U(-1/sqrt(taps), 1/sqrt(taps)) (``torch.nn.Conv1d``'s
    own, depthwise: HF ``_init_weights`` passes a Conv1d by); the head norm's
    scale 1; normal(0.02) for every Linear, `kda_wo` with the trunk's depth
    scaling. ``kda_wqkv``'s columns are [q | k | v] and ``kda_conv``'s the
    same: three convolutions side by side."""
    m, D = cfg.kda, cfg.d_model
    HK, R = m.d_inner, m.head_dim
    key = jax.random.split(jax.random.fold_in(ks[11], 5), 8)
    lo, hi, floor = m.dt_init
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key[0], (n, HK))
                             * np.log(hi / lo) + np.log(lo)), floor)
    bound = 1.0 / np.sqrt(m.d_conv)
    return {
        "kda_wqkv": _init_normal(ks[0], (n, D, 3 * HK), 0.02),
        "kda_conv": jax.random.uniform(key[1], (n, m.d_conv, 3 * HK),
                                       jnp.float32, -bound, bound),
        "kda_fa": _init_normal(key[2], (n, D, R), 0.02),
        "kda_fb": _init_normal(key[3], (n, R, HK), 0.02),
        "kda_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "kda_A_log": jnp.log(jax.random.uniform(
            key[4], (n, m.n_heads), jnp.float32, 1.0, 16.0)),
        "kda_wb": _init_normal(key[5], (n, D, m.n_heads), 0.02),
        "kda_ga": _init_normal(key[6], (n, D, R), 0.02),
        "kda_gb": _init_normal(key[7], (n, R, HK), 0.02),
        "kda_norm": jnp.ones((n, m.head_dim), jnp.float32),
        "kda_wo": _init_normal(ks[1], (n, HK, D),
                               0.02 / np.sqrt(2 * cfg.n_layers))}


def _kda_specs(cfg: TransformerConfig):
    """Replicated, as the mamba mixer: the scan is one program a device
    (``_kda`` refuses a mesh that cuts the sequence or the experts)."""
    return {name: P() for name in KDA_LEAVES}


GDN_LEAVES = ("gdn_wqkvz", "gdn_wba", "gdn_conv", "gdn_dt_bias", "gdn_A_log",
              "gdn_norm", "gdn_wo")


def _init_gdn(ks, cfg: TransformerConfig, n):
    """HF ``Qwen3NextGatedDeltaNet``: ``A_log`` = log U(0, 16) a value head
    (drawn in (0, 16]: log 0 is no weight), ``dt_bias`` ones, the head norm's
    scale ones (NOT zero-centred), the convolution's taps ``torch.nn.
    Conv1d``'s own (U(-1/sqrt(taps), 1/sqrt(taps)), depthwise), normal(0.02)
    for every Linear, ``gdn_wo`` with the trunk's depth scaling.
    ``gdn_wqkvz``'s columns are [q | k | v | z], every head of a part side by
    side (the checkpoint groups them by key head: the loader's matter),
    ``gdn_wba``'s [b | a], ``gdn_conv``'s [q | k | v]."""
    m, D = cfg.gdn, cfg.d_model
    key = jax.random.split(jax.random.fold_in(ks[11], 6), 3)
    bound = 1.0 / np.sqrt(m.d_conv)
    return {
        "gdn_wqkvz": _init_normal(ks[0], (n, D, m.conv_dim + m.v_inner), 0.02),
        "gdn_wba": _init_normal(key[0], (n, D, 2 * m.n_v_heads), 0.02),
        "gdn_conv": jax.random.uniform(key[1], (n, m.d_conv, m.conv_dim),
                                       jnp.float32, -bound, bound),
        "gdn_dt_bias": jnp.ones((n, m.n_v_heads), jnp.float32),
        "gdn_A_log": jnp.log(16.0 * (1.0 - jax.random.uniform(
            key[2], (n, m.n_v_heads), jnp.float32))),
        "gdn_norm": jnp.ones((n, m.v_dim), jnp.float32),
        "gdn_wo": _init_normal(ks[1], (n, m.v_inner, D),
                               0.02 / np.sqrt(2 * cfg.n_layers))}


def _gdn_specs(cfg: TransformerConfig):
    """Replicated, as the kda mixer: the scan is one program a device."""
    return {name: P() for name in GDN_LEAVES}


# the indexer's leaves of a "dsa" layer, beside the attention's own
DSA_LEAVES = ("wq_idx", "wk_idx", "k_idx_norm_scale", "k_idx_norm_bias",
              "ww_idx")


def _init_dsa(ks, cfg: TransformerConfig, n):
    """Grouped-query attention's leaves (``_init_attention``) and the
    indexer's: normal(0.02) for its three Linears, its key LayerNorm 1 and
    0."""
    m, D = cfg.dsa, cfg.d_model
    kq, kk, kw = jax.random.split(jax.random.fold_in(ks[11], 2), 3)
    return {
        **_init_attention(ks, cfg, n),
        "wq_idx": _init_normal(kq, (n, D, m.n_heads * m.head_dim), 0.02),
        "wk_idx": _init_normal(kk, (n, D, m.head_dim), 0.02),
        "k_idx_norm_scale": jnp.ones((n, m.head_dim), jnp.float32),
        "k_idx_norm_bias": jnp.zeros((n, m.head_dim), jnp.float32),
        "ww_idx": _init_normal(kw, (n, D, m.n_heads), 0.02)}


def _dsa_specs(cfg: TransformerConfig):
    """The attention's own, and the indexer replicated: its one key is
    every index head's (the mixer runs on one program, ``_dsa``)."""
    return {**_attention_specs(cfg), **{name: P() for name in DSA_LEAVES}}


def _init_run(ks, cfg: TransformerConfig, kind, n):
    """``n`` stacked layers of one kind: the two norms, the kind's mixer
    (``_KINDS``) and its MLP half (``experts_of``: dense at ``d_ff``, or the
    experts held here at ``d_ff_expert`` with their router). A single
    sublayer has its own half alone: ``ln1`` and the mixer, or (kind "mlp")
    ``ln2`` and the MLP."""
    D, E = cfg.d_model, experts_of(cfg, kind)
    F = (cfg.d_ff_expert or cfg.d_ff) if E else cfg.d_ff
    norm = _init_normal
    blocks = {}
    if mixer_of(kind) != "mlp":
        blocks.update({
            "ln1_scale": _init_norm_scale(cfg, (n, D)),
            "ln1_bias": jnp.zeros((n, D), jnp.float32),
            **_KINDS[mixer_of(kind)].init(ks, cfg, n)})
    if not has_mlp(kind):
        return blocks
    blocks.update({
        "ln2_scale": _init_norm_scale(cfg, (n, D)),
        "ln2_bias": jnp.zeros((n, D), jnp.float32)})
    if cfg.sandwich_norm:
        for name in ("ln1_post", "ln2_post"):
            blocks[name + "_scale"] = _init_norm_scale(cfg, (n, D))
            blocks[name + "_bias"] = jnp.zeros((n, D), jnp.float32)
    if cfg.mlp in GATED_MLPS:
        blocks["w3"] = norm(ks[8], (n, E, D, F) if E > 0 else (n, D, F),
                            0.02)
    out_scale = 0.02 / np.sqrt(2 * cfg.n_layers)
    if E > 0:
        blocks.update({
            "router": norm(ks[2], (n, D, cfg.router.width or E), 0.02),
            "w1": norm(ks[3], (n, E, D, F), 0.02),
            "b1": jnp.zeros((n, E, F), jnp.float32),
            "w2": norm(ks[4], (n, E, F, D), out_scale),
            "b2": jnp.zeros((n, E, D), jnp.float32),
        })
        if cfg.router.bias:
            blocks[ROUTER_BIAS] = jnp.zeros(
                (n, cfg.router.width or E), jnp.float32)
        if cfg.d_ff_shared:
            Fs = cfg.d_ff_shared
            k1, k3, k2 = jax.random.split(jax.random.fold_in(ks[3], 1), 3)
            blocks.update({"ws1": norm(k1, (n, D, Fs), 0.02),
                           "ws3": norm(k3, (n, D, Fs), 0.02),
                           "ws2": norm(k2, (n, Fs, D), out_scale)})
            if cfg.shared_gate:
                blocks["wsg"] = norm(jax.random.fold_in(ks[3], 2), (n, D, 1),
                                     0.02)
    else:
        blocks.update({
            "w1": norm(ks[3], (n, D, F), 0.02),
            "b1": jnp.zeros((n, F), jnp.float32),
            "w2": norm(ks[4], (n, F, D), out_scale),
            "b2": jnp.zeros((n, D), jnp.float32),
        })
    if cfg.mlp == "relu2":      # two matrices and nothing else
        for name in ("b1", "b2", "ws3"):
            blocks.pop(name, None)
    return blocks


def _init_trunk(ks, cfg: TransformerConfig):
    """Run 0 draws from ``ks`` as the homogeneous stack always has; a later
    run from its own fold of the spare ``ks[10]``."""
    return {
        "blocks": blocks_of_runs([
            _init_run(ks if r == 0 else jax.random.split(
                jax.random.fold_in(ks[10], r), 12), cfg, kind, n)
            for r, (kind, n) in enumerate(layer_runs(cfg))]),
        "lnf_scale": _init_norm_scale(cfg, (cfg.d_model,)),
        "lnf_bias": jnp.zeros((cfg.d_model,), jnp.float32),
    }


def init_params(rng, cfg: TransformerConfig):
    D, V = cfg.d_model, cfg.vocab_size
    ks = jax.random.split(rng, 12)
    params = _init_trunk(ks, cfg)
    params["embed"] = _init_normal(ks[5], (V, D), 0.02)
    if cfg.use_pos_emb:
        params["pos"] = _init_normal(ks[6], (cfg.max_seq_len, D), 0.02)
    if not cfg.tied_head:
        params["head"] = _init_normal(ks[7], (D, V), 0.02)
    if cfg.n_loops > 1:
        params["exit_gate_w"] = _init_normal(ks[9], (D,), 0.02)
        params["exit_gate_b"] = jnp.zeros((), jnp.float32)
    return params


def _run_specs(cfg: TransformerConfig, kind):
    moe = experts_of(cfg, kind) > 0
    blocks = {}
    if mixer_of(kind) != "mlp":
        blocks.update({"ln1_scale": P(None, None), "ln1_bias": P(None, None),
                       **_KINDS[mixer_of(kind)].specs(cfg)})
    if not has_mlp(kind):
        return blocks
    blocks.update({"ln2_scale": P(None, None), "ln2_bias": P(None, None)})
    if cfg.sandwich_norm:
        for name in ("ln1_post", "ln2_post"):
            blocks[name + "_scale"] = blocks[name + "_bias"] = P(None, None)
    if cfg.mlp in GATED_MLPS:
        blocks["w3"] = (P(None, "ep", None, "tp") if moe
                        else P(None, None, "tp"))
    if moe:
        blocks.update({
            "router": P(None, None, None),
            "w1": P(None, "ep", None, "tp"),
            "b1": P(None, "ep", "tp"),
            "w2": P(None, "ep", "tp", None),
            "b2": P(None, "ep", None),
        })
        if cfg.router.bias:
            blocks[ROUTER_BIAS] = P(None, None)
        if cfg.d_ff_shared:
            blocks.update({"ws1": P(None, None, "tp"),
                           "ws3": P(None, None, "tp"),
                           "ws2": P(None, "tp", None)})
            if cfg.shared_gate:
                blocks["wsg"] = P(None, None, None)
    else:
        blocks.update({
            "w1": P(None, None, "tp"),
            "b1": P(None, "tp"),
            "w2": P(None, "tp", None),
            "b2": P(None, None),
        })
    if cfg.mlp == "relu2":
        for name in ("b1", "b2", "ws3"):
            blocks.pop(name, None)
    return blocks


def param_specs(cfg: TransformerConfig):
    """PartitionSpecs: Megatron tp sharding; experts over ep; rest replicated
    (dp/sp shard activations, not weights)."""
    specs = {
        "embed": P(None, "tp"),
        "blocks": blocks_of_runs(
            [_run_specs(cfg, kind) for kind, _ in layer_runs(cfg)]),
        "lnf_scale": P(None),
        "lnf_bias": P(None),
    }
    if cfg.use_pos_emb:
        specs["pos"] = P(None, "tp")
    if not cfg.tied_head:
        specs["head"] = P(None, "tp")
    if cfg.n_loops > 1:
        specs["exit_gate_w"], specs["exit_gate_b"] = P(None), P()
    return specs


def _constrain(x, mesh, *spec):
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _dropout(x, rate, rng):
    """Inverted dropout; identity when rate == 0 or rng is None (eval)."""
    if rate == 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def _gelu(x, cfg: TransformerConfig):
    # HF BERT's "gelu" is the exact erf form; jax.nn.gelu defaults to the
    # tanh approximation (fine for training-from-scratch, wrong for
    # checkpoint-exact parity)
    if not cfg.gelu_exact:
        return jax.nn.gelu(x)
    # ONE float32 erf, as HF's and torch's "gelu" is written. jax.nn.gelu's
    # exact form is 0.5 * x * erfc(-x / sqrt(2)) in x's dtype, and erfc has
    # no HLO opcode: it expands to a two-branch rational with an exponential,
    # some seventy vector operations an element (the BERT step on a v5e, PR
    # 62: 418 -> 388 ms). In float32 whatever x is: 1 + erf cancels below
    # x = -2, and the TPU's vector unit computes in float32 either way
    return _gelu_erf(x)


def _one_plus_erf(x32):
    """Twice the normal CDF: the exact GELU is half of x times this."""
    return 1.0 + jax.lax.erf(x32 * math.sqrt(0.5))


@jax.custom_jvp
def _gelu_erf(x):
    x32 = x.astype(jnp.float32)
    return (0.5 * x32 * _one_plus_erf(x32)).astype(x.dtype)


@_gelu_erf.defjvp
def _gelu_erf_jvp(primals, tangents):
    """(GELU(x), t GELU'(x)), GELU' = Phi + x phi computed in float32 and
    rounded once to x's dtype, as every activation the step stores is. The
    rule ENDS IN A BARRIER over the pair: under the trunk's `remat` the
    recomputed `w1` fusion then evaluates `erf` and `exp` once and hands u
    and GELU' over, the `w2` weight gradient reads u and dU is one multiply.
    Without it (autodiff's own derivative, or this rule bare, or a
    `custom_vjp`: the same compiled text, a rule's boundary is gone before
    XLA fuses) the recomputed pass hands `w1 x + b1` over and each of the
    two re-derives an `erf` beside its matmul: four evaluations a layer for
    two, 8.5 + 8.4 ms of a 388 ms BERT step on a v5e (PERF.md, PR 65:
    `recompute` + 8.6 ms, `bwd` - 16.9, + 2.0 to 3.0 % tokens a second).
    Outside differentiation the primal function above runs alone; a
    differentiated step's forward pass runs this rule, u is that function's
    value bit for bit, and the compiler drops the GELU' nothing reads."""
    (x,), (t,) = primals, tangents
    x32 = x.astype(jnp.float32)
    s = _one_plus_erf(x32)
    d = 0.5 * s + x32 * (jnp.exp(-0.5 * x32 * x32)
                         / math.sqrt(2.0 * math.pi))
    u, d = jax.lax.optimization_barrier(
        ((0.5 * x32 * s).astype(x.dtype), d.astype(x.dtype)))
    return u, t * d


def _rms_norm32(x, scale, eps):
    """RMSNorm in float32 as computed: the statistic, the scaling and the
    result before any cast."""
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return x32 * scale


def _rms_norm(x, scale, eps):
    return _rms_norm32(x, scale, eps).astype(x.dtype)


def _rms_norm_heads(x, scale, eps, offset=False):
    """RMSNorm over each head's channels: x (B, T, heads * hd), the heads
    side by side; ``scale`` (hd,) is every head's (``offset``: the stored w
    of 1 + w). The statistic alone takes the (B, T, heads, hd) view; x keeps
    its layout."""
    hd = scale.shape[-1]
    if offset:
        scale = 1.0 + scale
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32.reshape(x.shape[:-1] + (-1, hd))), -1)
    x32 = x32 * jnp.repeat(jax.lax.rsqrt(ms + eps), hd, axis=-1)
    return (x32 * jnp.tile(scale, x.shape[-1] // hd)).astype(x.dtype)


def _rms_norm_groups(x, scale, groups, eps):
    """RMSNorm over each of ``groups`` runs of x's channels, side by side:
    x (B, T, C) float32, ``scale`` (C,) the whole width's; -> float32. The
    statistic alone takes the (B, T, groups, C / groups) view."""
    size = x.shape[-1] // groups
    ms = jnp.mean(jnp.square(x.reshape(x.shape[:-1] + (groups, size))), -1)
    return x * jnp.repeat(jax.lax.rsqrt(ms + eps), size, axis=-1) * scale


def _norm(x, scale, bias, cfg: TransformerConfig):
    """Dialect-dispatched normalization: LayerNorm (default) or RMSNorm
    (Llama family — ``bias`` exists in the pytree but is ignored). The norm
    of the residual stream, wherever a block, ``encode`` or the head calls
    it: ``SCOPE_BLK_NORM``."""
    with jax.named_scope(SCOPE_BLK_NORM):
        if cfg.norm == "rmsnorm":
            if cfg.norm_offset:     # zero-centred: the stored weight is w
                scale = 1.0 + scale
            return _rms_norm(x, scale, cfg.ln_eps)
        return _layer_norm(x, scale, bias, cfg.ln_eps)


def _rope(x, pos0, theta, hd, rot=0, yarn=None):
    """Rotary position embeddings, HF rotate_half convention: x (B, T,
    heads*hd), the heads side by side as the projection writes them, at
    absolute positions pos0..pos0+T-1; each head's hd columns split into
    two halves rotated by position-dependent angles. A column's partner
    lies hd/2 columns to its right (first half) or left (second half), in
    the same head, so the halves swap by two rolls of the whole axis and no
    (B, T, heads, hd) array, which the TPU would lay out anew, is made.

    ``rot`` (0 = hd): only a head's FIRST ``rot`` columns turn, rotate-half
    inside them (partners rot/2 apart), and the others pass (HF
    ``partial_rotary_factor``): their cos is 1 and their sin 0. ``yarn``:
    the table's frequencies are ``yarn_inv_freq``'s (made in float64, cast)
    and cos and sin carry its ``attention_factor`` (``kernels/rope.py``:
    ``tables_halves``, the one expression this and the kernel that turns a
    block in VMEM read). Tables and rotation are float32 either way; with
    the defaults this is the program it was."""
    B, T, W = x.shape
    rot = rot or hd
    cos, sin = rope_kernel.tables_halves(T, pos0, theta, hd, rot, yarn,
                                        W // hd)                # (T, W)
    x32 = x.astype(jnp.float32)
    first = jnp.arange(W) % hd < rot // 2
    partner = jnp.where(first, jnp.roll(x32, -(rot // 2), -1),
                        jnp.roll(x32, rot // 2, -1))
    return (x32 * cos + partner * sin).astype(x.dtype)


def _rope_interleaved(x, pos0, theta, hd, first):
    """Rotary position embeddings on PART of each head, in the interleaved
    convention (DeepSeek's ``rope_interleave``): x (B, T, heads*hd), the
    heads side by side; of a head's hd columns those from ``first`` on are
    rotary, in adjacent pairs (2i, 2i+1) turned by the angle of frequency i
    of hd - first; the columns before ``first`` pass as they are. A column's
    partner lies one column to its right (even) or left (odd): two rolls of
    the whole axis by one, and no (B, T, heads, hd) array is made.

    HF's ``apply_rotary_pos_emb_interleave`` moves a head's even columns to
    its first half and the odd ones to the second and rotates halves; here a
    pair stays where it is. The two results are one permutation of a head's
    rotary columns apart, the same for q and k, so every q . k is the same
    sum in another order."""
    B, T, W = x.shape
    cos, sin = rope_kernel.tables(T, pos0, theta, hd, first, W // hd)
    x32 = x.astype(jnp.float32)
    even = (jnp.arange(W) % hd - first) % 2 == 0
    partner = jnp.where(even, jnp.roll(x32, -1, -1), jnp.roll(x32, 1, -1))
    return (x32 * cos + partner * sin).astype(x.dtype)


def _is_key_padding_bias(attn_bias):
    """A (B, 1, 1, T) additive bias is per-KEY (the padding-mask form BERT
    builds from input_mask) — the flash kernel folds it into its score
    blocks. Any other bias shape needs the unfused path."""
    return (attn_bias is not None and attn_bias.ndim == 4
            and attn_bias.shape[1] == 1 and attn_bias.shape[2] == 1)


def _resolve_attn_impl(cfg: TransformerConfig, mesh, T, attn_bias=None):
    impl = cfg.attn_impl
    if attn_bias is not None and not _is_key_padding_bias(attn_bias):
        # only the unfused path applies a general additive bias; an
        # explicitly requested fused/ring impl must not degrade SILENTLY —
        # such batches materialize full (B, nh, T, T) f32 scores per layer
        if impl not in ("auto", "dot"):
            import warnings
            warnings.warn(
                f"attn_impl={impl!r} requested but a non-key-padding "
                "attn_bias is present: falling back to the unfused 'dot' "
                "path", stacklevel=3)
        return "dot"
    if attn_bias is not None and impl == "flash" and T % min(128, T):
        # masked configs used to ride the unfused fallback regardless of T;
        # keep that grace instead of letting the kernel's block-divisibility
        # check raise on a previously-working masked batch
        import warnings
        warnings.warn(
            f"attn_impl='flash' with a padding mask needs seq_len divisible "
            f"by 128 (got {T}): falling back to the unfused 'dot' path",
            stacklevel=3)
        return "dot"
    if impl != "auto":
        return impl
    if mesh is not None and "sp" in mesh.axis_names and mesh.shape["sp"] > 1:
        return "ring"   # key-padding biases rotate with the k/v chunks
    if jax.default_backend() == "tpu" and T % 128 == 0:
        return "flash"
    return "dot"


def _key_bias(attn_bias, B):
    """(B, 1, 1, T) key-padding bias -> the (B, T) per-key form the fused
    paths share; a broadcast-batch (1, 1, 1, T) mask expands to the real
    batch so dp/sp sharding of the bias is always well-formed."""
    if attn_bias is None:
        return None
    kb = attn_bias.reshape(attn_bias.shape[0], attn_bias.shape[-1])
    if kb.shape[0] == 1 and B > 1:
        kb = jnp.broadcast_to(kb, (B, kb.shape[1]))
    return kb


def _flash(qkv, cfg: TransformerConfig, mesh, kb, window=None):
    """The fused Pallas kernels on the projection's own layout: ``qkv`` is
    the (B, T, 3*D) projection as it stands, or three (B, T, D) arrays;
    -> (B, T, D), what ``wo`` reads. Nothing is transposed either way.
    ``window``: a "window" layer's (``flash_attention_btd``)."""
    from ..kernels.flash_attention import flash_attention_btd
    if mesh is None or mesh.size == 1:
        return flash_attention_btd(qkv, cfg.n_heads, cfg.causal, k_bias=kb,
                                   window=window)
    # A Mosaic kernel has no partitioning rule and only lowers in a fully
    # manual context, so under a mesh it runs per shard: attention is
    # independent per (batch, head), which is how the arrays are laid out
    # here (batch over dp, head columns over tp, full sequence).
    spec = P("dp", None, "tp")
    heads = cfg.n_heads // mesh.shape["tp"]

    def per_shard(qkv, *kb):
        return flash_attention_btd(qkv, heads, cfg.causal,
                                   k_bias=kb[0] if kb else None,
                                   window=window)

    bias = () if kb is None else (kb,)
    return jax.shard_map(
        per_shard, mesh=mesh, check_vma=False,
        in_specs=(spec,) + (P("dp", None),) * len(bias),
        out_specs=spec)(qkv, *bias)


def _attention_core(q, k, v, cfg: TransformerConfig, mesh, impl,
                    attn_bias=None, window=None):
    """q/k/v: (B, T, heads * width), every head's columns side by side ->
    (B, T, heads * v's width): D everywhere but for latent attention, whose
    q and k are wider than its v. Three paths:
    - ring: sequence-parallel exact attention over the sp axis (shard_map +
      ppermute ring, hetu_tpu/parallel/ring_attention.py)
    - flash: fused Pallas online-softmax kernel (hetu_tpu/kernels); folds a
      key-padding ``attn_bias`` (B, 1, 1, T) into its score blocks
    - dot: unfused reference form (the reference framework's
      BatchMatMul+Softmax attention); applies any additive ``attn_bias``
    ``window``: query t keeps the keys t - window < s <= t. The flash
    kernels stop their loops at its edge, the dot path masks; the ring, whose
    exchange a window would cut short, refuses."""
    B, T, _ = q.shape
    nh = cfg.n_heads
    hd = q.shape[-1] // nh
    kb = _key_bias(attn_bias, B)
    if impl == "flash":
        return _flash((q, k, v), cfg, mesh, kb, window)
    q, k, v = (x.reshape(B, T, nh, -1) for x in (q, k, v))
    if impl == "ring":
        if window is not None:
            raise NotImplementedError(
                f"a window layer (window={window}) on a mesh that shards "
                "the sequence (sp > 1): the ring passes every k/v chunk to "
                "every device; a window's exchange is with the neighbours "
                "that hold its keys alone, and is not written")
        from ..parallel.ring_attention import ring_attention
        # the ring works on (B, nh, T, hd) chunks: transposed here, locally
        spec = P("dp", "tp", "sp", None)
        fn_part = functools.partial(ring_attention, axis_name="sp",
                                    causal=cfg.causal)
        # the bias shards like k's sequence axis; each column rotates
        # around the ring with its k/v chunk
        bias = () if kb is None else (kb,)
        fn = jax.shard_map(
            fn_part, mesh=mesh,
            in_specs=(spec,) * 3 + (P("dp", "sp"),) * len(bias),
            out_specs=spec)
        out = fn(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)), *bias)
        return out.transpose(0, 2, 1, 3).reshape(B, T, -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(hd)
    if cfg.causal:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        scores = jnp.where(kpos <= qpos, scores, -1e30)
        if window is not None:
            scores = jnp.where(kpos > qpos - window, scores, -1e30)
    if attn_bias is not None:
        scores = scores + attn_bias.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(B, T, -1)


# the bytes of q-wide rotary tables (cos and sin, float32), held through the
# whole step, PAST which `_split_heads` turns q in k-wide column groups: a
# 32nd of a v5e's 16 GiB. The widest of the benchmark's rotary cells before
# PR 49 holds exactly this (32 heads of 128 at 16,384 tokens) and, like the
# narrower ones, lowers as it did; a `perf_opt` PR that measures those cells
# may lower the bound
ROPE_TABLE_BYTES = 512 << 20


def _split_heads(qkv, p, cfg: TransformerConfig, mesh, impl):
    """The (B, T, (nh + 2 nkv) hd) projection -> q, k, v (B, T, D) as every
    attention impl takes them: cut, QK-normed, rotated, scaled, named for
    the trunk's ``remat`` and the kv heads broadcast to their query groups."""
    B, T, _ = qkv.shape
    nh, hd, nkv = cfg.n_heads, cfg.head_dim, cfg.kv_heads
    # cut along the columns; a head stays hd columns of its array
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    if cfg.qk_norm == "head":
        q = _rms_norm_heads(q, p["q_norm"], cfg.ln_eps, cfg.norm_offset)
        k = _rms_norm_heads(k, p["k_norm"], cfg.ln_eps, cfg.norm_offset)
    elif cfg.qk_norm:
        # the statistic runs over every head of the projection at once
        q = _rms_norm(q, p["q_norm"], cfg.ln_eps)
        k = _rms_norm(k, p["k_norm"], cfg.ln_eps)
    if impl != "ring":
        # Ulysses-style: gather k/v over sp, heads stay tp-sharded
        # (the ring keeps them sequence-sharded and rotates chunks)
        k = _constrain(k, mesh, "dp", None, "tp")
        v = _constrain(v, mesh, "dp", None, "tp")
    if cfg.rope:
        rope = (0, cfg.rope_theta, hd, cfg.rope_dim, cfg.rope_yarn)
        # q and k where they stand in the projection, if nothing has touched
        # them: the kernel reads a column range and no slice is copied
        cut = ((q, None), (k, None)) if cfg.qk_norm else (
            (qkv, (0, nh * hd)), (qkv, (nh * hd, nkv * hd)))
        if all(rope_kernel.takes(x, hd, mesh=mesh, rot=cfg.rope_dim, at=at)
               for x, at in cut):
            # one pass through VMEM each, q whole (``kernels/rope.py``: a
            # TPU, one program, a shape its blocks divide; its tables are a
            # lane tile wide), under the rotation's own scope in every model
            with jax.named_scope(SCOPE_ATTN_ROPE):
                q, k = (rope_kernel.rope_halves(x, *rope, at=at)
                        for x, at in cut)
        elif nh > nkv and 2 * 4 * T * nh * hd > ROPE_TABLE_BYTES:
            # `_rope` tiles its float32 cos and sin tables to its input's
            # width, and XLA hoists them out of the layer scan and holds them
            # for the whole step: 2 x 512 MiB for 64 heads of 128 at 16,384
            # tokens, where k's are 2 x 64 MiB. Past the bound q turns a
            # group of k's width at a time, so that q and k read ONE pair of
            # (T, kv_heads * hd) tables, under a scope of the rotation's own
            # (PERF.md section 6, PR 49). Under it the whole-width call
            # stays: the rotary cells the benchmark had lower as they did
            with jax.named_scope(SCOPE_ATTN_ROPE):
                q = jnp.concatenate(
                    [_rope(q[..., i:i + nkv * hd], *rope)
                     for i in range(0, nh * hd, nkv * hd)], -1)
                k = _rope(k, *rope)
        else:
            # rotate BEFORE any gqa broadcast (rope is per-kv-head)
            q = _rope(q, *rope)
            k = _rope(k, *rope)
    if cfg.multipliers.attention is not None:
        # every impl scales its scores by 1/sqrt(hd): q carries the
        # rest (Granite: 1/64 at hd = 64, so q * 0.125, exact)
        q = q * jnp.asarray(cfg.multipliers.attention * np.sqrt(hd),
                            q.dtype)
    # what the attention's backward pass reads again. They are the flash
    # `custom_vjp`'s INPUTS, so its residual IS the named value and a name
    # on the caller's side is enough (o and lse are made inside the call and
    # had to be named in its forward rule, `_flash_fwd`). Before the repeat:
    # k and v are kept at `kv_heads`, the broadcast is a copy to run again
    q, k, v = (checkpoint_name(x, name) for x, name in (
        (q, REMAT_ATTN_Q), (k, REMAT_ATTN_K), (v, REMAT_ATTN_V)))
    if nkv != nh:
        # grouped-query: broadcast each kv head to its query group;
        # every attention impl then sees matching head counts
        k, v = (jnp.repeat(x.reshape(B, T, nkv, hd), nh // nkv,
                           axis=2).reshape(B, T, nh * hd) for x in (k, v))
    return q, k, v


def _projection_in_place(cfg: TransformerConfig, mesh, impl):
    """Whether the flash kernels read the fused [q | k | v] projection where
    it stands: nothing touches q or k on the way and the columns lie on one
    shard. Otherwise ``_split_heads`` makes q, k, v arrays of their own."""
    tp = 1 if mesh is None else mesh.shape.get("tp", 1)
    return (impl == "flash" and cfg.kv_heads == cfg.n_heads and tp == 1
            and not (cfg.qk_norm or cfg.rope
                     or cfg.multipliers.attention is not None))


def _attention(h, p, cfg: TransformerConfig, mesh, attn_bias=None,
               window=None):
    """Grouped-query attention on a layer's normed input. ``window``: a
    "window" layer's (``_window``), whose core runs under ``SCOPE_SWA_ATTN``
    where a full layer's runs under ``SCOPE_BLK_ATTN``."""
    B, T, _ = h.shape
    impl = _resolve_attn_impl(cfg, mesh, T, attn_bias)
    with jax.named_scope(SCOPE_BLK_QKV):
        qkv = jnp.einsum("btd,de->bte", h, p["wqkv"].astype(h.dtype),
                         preferred_element_type=jnp.float32).astype(h.dtype)
        if cfg.attn_proj_bias:
            qkv = qkv + p["bqkv"].astype(h.dtype)
    if _projection_in_place(cfg, mesh, impl):
        # no name for `remat` here: a kept projection would be copied out
        # of its stack a layer for the kernels (tracing.REMAT_CANDIDATES)
        with jax.named_scope(SCOPE_BLK_ATTN):
            out = _flash(qkv, cfg, mesh, _key_bias(attn_bias, B))
    else:
        with jax.named_scope(SCOPE_BLK_QKV):
            q, k, v = _split_heads(qkv, p, cfg, mesh, impl)
        with jax.named_scope(SCOPE_BLK_ATTN if window is None
                             else SCOPE_SWA_ATTN):
            out = _attention_core(q, k, v, cfg, mesh, impl, attn_bias,
                                  window)
    if impl != "flash":
        # the flash kernel names its o itself, with its lse, where they
        # become its backward pass's residuals (`_flash_fwd`)
        out = checkpoint_name(out, REMAT_ATTN_O)
    if cfg.attn_gate:
        with jax.named_scope(SCOPE_ATTN_GATE):
            out = _gate_heads(out, h, p["wg"], cfg.head_dim)
    with jax.named_scope(SCOPE_BLK_WO):
        out = jnp.einsum("btd,de->bte", out, p["wo"].astype(h.dtype),
                         preferred_element_type=jnp.float32).astype(h.dtype)
        if cfg.attn_proj_bias:
            out = out + p["bo"].astype(h.dtype)
    return out


def _gate_heads(o, x, wg, hd):
    """The gate on attention's output: o (B, T, heads * hd) times sigmoid(x
    Wg), x the layer's normed input: ``wg`` (D, heads) a head's one gate on
    its hd columns (Laguna), or (D, heads * hd) a gate a COLUMN, the heads'
    side by side as o's are (Qwen3-Next). The logits accumulate in float32
    and the sigmoid and the product are float32; the result is o's dtype."""
    g = jax.nn.sigmoid(jnp.einsum("btd,dh->bth", x, wg.astype(x.dtype),
                                  preferred_element_type=jnp.float32))
    o32 = o.astype(jnp.float32)
    if g.shape[-1] != o.shape[-1]:      # a head's one gate on its columns
        g = jnp.repeat(g, hd, axis=-1)
    return (o32 * g).astype(o.dtype)


def _window(h, p, cfg: TransformerConfig, mesh, attn_bias=None):
    """A sliding-window layer (``cfg.window``): ``_attention`` at the window
    kind's own head count and rotary form, in which query t keeps the keys t
    - window < s <= t. With window >= T it is ``_attention`` at those
    sizes."""
    return _attention(h, p, _window_view(cfg), mesh, attn_bias,
                      window=cfg.window.window)


def _mla_keys(k_nope, k_rope, nh):
    """Every head's k: its own ``k_nope`` columns beside the ONE rotary key
    of the token, (B, T, nh * nope) and (B, T, rope) -> (B, T, nh * (nope +
    rope)). One concatenation of column slices along the lanes: no (B, T,
    heads, hd) array; the cotangent is the slices back and a sum over the
    heads for the shared key."""
    nope = k_nope.shape[-1] // nh
    return jnp.concatenate(
        [part for i in range(nh)
         for part in (k_nope[..., i * nope:(i + 1) * nope], k_rope)], -1)


def _rope_q(q, cfg: TransformerConfig, mesh):
    """The rotary columns of latent attention's q (B, T, nh * qk_dim), each
    head's from ``nope_dim`` on: in one pass through VMEM where the kernel
    serves the call (``kernels/rope.py``: a TPU, one program, a shape its
    blocks divide), else ``_rope_interleaved``, the expression the kernel is
    held to."""
    m = cfg.mla
    rotate = (rope_kernel.rope_interleaved
              if rope_kernel.takes(q, m.qk_dim, m.nope_dim, mesh)
              else _rope_interleaved)
    return rotate(q, 0, cfg.rope_theta, m.qk_dim, m.nope_dim)


def _mla_qkv(h, p, cfg: TransformerConfig, mesh=None):
    """Latent attention's projections -> (q (B, T, nh * qk_dim), k the same,
    v (B, T, nh * v_dim), (the latent (B, T, kv_rank) as ``wkv_a`` writes
    it, its RMSNorm in float32 before ``wkv_b``'s cast)) as
    HF ``DeepseekV3Attention`` computes them (``q_lora_rank`` None):
    q = h Wq, a head [q_nope | q_rope]; [c | k_rope] = h Wkv_a; [k_nope | v]
    = RMSNorm(c) Wkv_b; q_rope (``_rope_q``) and the one k_rope a token
    (``_rope_interleaved``: 64 columns, narrower than a lane tile) rotated
    (``MLAConfig.rotate``; false: neither, and every other line as it is);
    k = [k_nope | k_rope], the rotary key every head's. The matmuls read
    bf16 operands; the latent's norm is float32."""
    m, nh = cfg.mla, cfg.n_heads
    proj = lambda x, w: jnp.einsum(
        "btd,de->bte", x, w.astype(h.dtype),
        preferred_element_type=jnp.float32).astype(h.dtype)
    with jax.named_scope(SCOPE_MLA_Q):
        q = proj(h, p["wq"])
        if m.rotate:
            q = _rope_q(q, cfg, mesh)
    with jax.named_scope(SCOPE_MLA_KV_DOWN):
        raw, k_rope = jnp.split(proj(h, p["wkv_a"]), [m.kv_rank], axis=-1)
        latent = _rms_norm32(raw, p["kv_norm"], cfg.ln_eps)
        c = checkpoint_name(latent.astype(h.dtype), REMAT_MLA_LATENT)
        if m.rotate:
            k_rope = _rope_interleaved(k_rope, 0, cfg.rope_theta, m.rope_dim,
                                       0)
        k_rope = checkpoint_name(k_rope, REMAT_MLA_LATENT)
    with jax.named_scope(SCOPE_MLA_KV_UP):
        k_nope, v = jnp.split(proj(c, p["wkv_b"]), [nh * m.nope_dim], axis=-1)
        k = _mla_keys(k_nope, k_rope, nh)
    return q, k, v, (raw, latent)


def _mla(h, p, cfg: TransformerConfig, mesh, attn_bias=None):
    """Multi-head latent attention (``cfg.mla``): ``_mla_qkv``, then softmax(
    q k^T / sqrt(qk_dim)) v a head at the two widths (scores qk_dim wide,
    values v_dim: the flash kernels take both, the ``dot`` path reshapes each
    array by its own), then `wo` from nh * v_dim. The three projections'
    scopes nest inside ``SCOPE_BLK_QKV``."""
    impl = _resolve_attn_impl(cfg, mesh, h.shape[1], attn_bias)
    if impl == "ring":
        raise NotImplementedError(
            "latent attention on a mesh that shards the sequence (sp > 1): "
            "the ring takes one head width for q, k and v")
    with jax.named_scope(SCOPE_BLK_QKV):
        q, k, v, _ = _mla_qkv(h, p, cfg, mesh)
        # the flash `custom_vjp`'s inputs, as on `_split_heads`' path
        q, k, v = (checkpoint_name(x, name) for x, name in (
            (q, REMAT_ATTN_Q), (k, REMAT_ATTN_K), (v, REMAT_ATTN_V)))
    with jax.named_scope(SCOPE_BLK_ATTN):
        out = _attention_core(q, k, v, cfg, mesh, impl, attn_bias)
    if impl != "flash":
        out = checkpoint_name(out, REMAT_ATTN_O)
    with jax.named_scope(SCOPE_BLK_WO):
        return jnp.einsum("bte,ed->btd", out, p["wo"].astype(h.dtype),
                          preferred_element_type=jnp.float32).astype(h.dtype)


def _dsa_index(h, p, cfg: TransformerConfig):
    """The indexer's three projections of a layer's normed input ``h`` (B,
    T, D), DETACHED from the trunk -> (qI (B, T, J * c) rotated, kI (B, T,
    c) LayerNormed then rotated, w (B, T, J) float32 = h Ww / sqrt(J c)):
    DeepSeek-V3.2-Exp's, whose q comes from a low-rank latent this model has
    not: RoPE (``_rope``, the model's theta) on all c columns, every head's
    the same way."""
    m = cfg.dsa
    x = jax.lax.stop_gradient(h)
    proj = lambda w: jnp.einsum("btd,de->bte", x, w.astype(x.dtype),
                                preferred_element_type=jnp.float32)
    # in one pass through VMEM where the kernel serves the call (the J
    # query heads side by side; the ONE key head of 64 columns is narrower
    # than a lane tile and takes the reference). No mesh: `_dsa_parts`
    rotate = lambda y: (
        rope_kernel.rope_halves
        if rope_kernel.takes(y, m.head_dim, rot=0) else _rope)(
            y, 0, cfg.rope_theta, m.head_dim)
    qI = rotate(proj(p["wq_idx"]).astype(x.dtype))
    kI = rotate(_layer_norm(proj(p["wk_idx"]).astype(x.dtype),
                            p["k_idx_norm_scale"], p["k_idx_norm_bias"],
                            cfg.ln_eps))
    w = proj(p["ww_idx"]) * (m.n_heads * m.head_dim) ** -0.5
    return qI, kI, w


def _dsa_parts(h, p, cfg: TransformerConfig, mesh, attn_bias=None):
    """Learned sparse attention (``cfg.dsa``) on a layer's normed input ->
    (the mixer's output (B, T, D), L_I the indexer's loss of the layer, kept
    (B,) the (query, key) pairs kept a sequence).

    Grouped-query attention as ``_attention`` computes it (``_split_heads``:
    QK-norm, RoPE, the kv heads broadcast), but a query attends to the
    ``top_k`` keys s <= t its indexer ranks highest (``kernels/dsa.py``): the
    selection is exact and has no gradient, so the trunk's gradients are
    those of attention under a constant mask; the indexer reads the layer's
    input detached and learns from L_I alone, whose target, the attention's
    head-summed probabilities, is detached too: L_I's gradient ends at the
    indexer's leaves and is made with it, in the forward pass
    (``dsa.indexer_loss``). The flash kernels take the
    kept set one bit a pair and compute every tile below the diagonal (a
    dense kernel under a mask); off the chip the ``dot`` path adds the same
    mask as a bias. With ``top_k`` >= T the kept set is the causal triangle
    and the output is ``_attention``'s. The kept set by query bears
    ``REMAT_DSA_MASK`` and the one by key is made from it here
    (``dsa.by_key_of``: only ``flash_bwd_dqkv`` reads it, so the forward pass
    never builds it): under the trunk's checkpoint, where ``_remat_names``
    admits the name, the backward pass reads the forward pass's own bits,
    turns them by key, and runs the indexer and the selection no second time
    (without the policy the name is an identity)."""
    from ..kernels import dsa
    from ..kernels.flash_attention import (flash_attention_btd,
                                           unpack_row_mask)
    if attn_bias is not None or (mesh is not None and mesh.size > 1):
        raise NotImplementedError(
            "learned sparse attention (dsa) on a mesh or under a padding "
            "mask: the selection, its packed masks and the indexer's loss "
            "run on one program over whole causal sequences")
    B, T, _ = h.shape
    nh, hd = cfg.n_heads, cfg.head_dim
    impl = _resolve_attn_impl(cfg, mesh, T)
    with jax.named_scope(SCOPE_BLK_QKV):
        qkv = jnp.einsum("btd,de->bte", h, p["wqkv"].astype(h.dtype),
                         preferred_element_type=jnp.float32).astype(h.dtype)
        if cfg.attn_proj_bias:
            qkv = qkv + p["bqkv"].astype(h.dtype)
        q, k, v = _split_heads(qkv, p, cfg, mesh, impl)
    with jax.named_scope(SCOPE_DSA_PROJ):
        qI, kI, w = _dsa_index(h, p, cfg)
    with jax.named_scope(SCOPE_DSA_SELECT):
        (by_query, _), kept = dsa.select(qI, kI, w, cfg.dsa.top_k)
        # by name: where the checkpoint keeps the bits (`_remat_names`) the
        # backward pass turns THEM by key and runs nothing of the indexer
        # again. `by_key` has to come from the named array: from the
        # unnamed one, the backward pass would need the selection to make it
        by_query = checkpoint_name(by_query, REMAT_DSA_MASK)
        row_mask = (by_query, dsa.by_key_of(by_query))
    with jax.named_scope(SCOPE_BLK_ATTN):
        if impl == "flash":
            out, lse = flash_attention_btd((q, k, v), nh, True,
                                           row_mask=row_mask)
        else:
            # `_attention_core`'s dot path, the kept set where it has the
            # causal triangle: the same numbers while they are the same set
            keep = unpack_row_mask(row_mask[0])[:, None]
            scores = jnp.where(keep, jnp.einsum(
                "bqhd,bkhd->bhqk", q.reshape(B, T, nh, hd),
                k.reshape(B, T, nh, hd),
                preferred_element_type=jnp.float32) / np.sqrt(hd), -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            out = checkpoint_name(jnp.einsum(
                "bhqk,bkhd->bqhd", probs, v.reshape(B, T, nh, hd),
                preferred_element_type=jnp.float32).astype(q.dtype).reshape(
                    B, T, -1), REMAT_ATTN_O)
            lse = jax.nn.logsumexp(scores, axis=-1).reshape(B * nh, 1, T)
    with jax.named_scope(SCOPE_DSA_LOSS):
        stop = jax.lax.stop_gradient
        # k at the k/v heads again: `_split_heads` broadcast each to its
        # query group, side by side
        k_kv = k.reshape(B, T, cfg.kv_heads, -1, hd)[:, :, :, 0].reshape(
            B, T, -1)
        # the indexer again, as a whole (one computation with the
        # selection's to the compiler): the loss's rule makes its leaves'
        # gradient where it makes the loss
        loss = dsa.indexer_loss(
            functools.partial(_dsa_index, cfg=cfg),
            {name: p[name] for name in DSA_LEAVES}, h, stop(q), stop(k_kv),
            stop(lse), row_mask[0], nh, 1.0 / np.sqrt(hd))
    with jax.named_scope(SCOPE_BLK_WO):
        out = jnp.einsum("btd,de->bte", out, p["wo"].astype(h.dtype),
                         preferred_element_type=jnp.float32).astype(h.dtype)
        if cfg.attn_proj_bias:
            out = out + p["bo"].astype(h.dtype)
    return out, loss, kept


def _dsa(h, p, cfg: TransformerConfig, mesh, attn_bias=None):
    """The "dsa" mixer -> (output, L_I): a mixer WITH a loss of its own
    (``_Kind.side_loss``)."""
    return _dsa_parts(h, p, cfg, mesh, attn_bias)[:2]


def _ssm_dt(dt_raw, dt_bias):
    """The step sizes, float32: softplus(raw + bias) a head, limit (0, inf)
    as published (no clamp)."""
    return jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)


def _ssm_log_decay(dt, A_log):
    """(B, c, Q, H) step sizes -> the log-decay dt * A a step, A = -exp(A_log)
    a head, cumulated over each chunk's positions, float32."""
    return jnp.cumsum(dt * -jnp.exp(A_log.astype(jnp.float32)), axis=2)


def _ssm_states(Bm, xd, chunk_decay):
    """-> (the state each chunk's own positions build, (B, c, G, R, P, N):
    sum_s B_s (x) xd_s with xd = x dt decayed to the chunk's end, bf16 operands,
    float32 sums; the state ENTERING each chunk, same shape: the recurrence
    S_c = chunk_decay_c S_{c-1} + local_c over the chunks, float32)."""
    local = jnp.einsum("bcsgn,bcsgrp->bcgrpn", Bm, xd,
                       preferred_element_type=jnp.float32)

    def carry_on(S, xs):
        decay, new = xs
        return decay[..., None, None] * S + new, S

    _, entering = jax.lax.scan(
        carry_on, jnp.zeros_like(local[:, 0]),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(local, 1, 0)))
    return local, jnp.moveaxis(entering, 0, 1)


def _ssd_chunks(x, dt, A_log, Bm, Cm, chunk):
    """x (B, T, H, P), dt (B, T, H), Bm/Cm (B, T, G, N) cut into T / chunk
    chunks of Q positions, the heads as (G groups, R heads a group) -> (x (B,
    c, Q, G, R, P), dt and the cumulative log-decay (B, c, Q, G, R), Bm, Cm
    (B, c, Q, G, N))."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    if T % chunk:
        raise ValueError(
            f"seq_len={T} is not a multiple of the ssm chunk={chunk}: the "
            "chunked scan pads nothing")
    c, Q, R = T // chunk, chunk, H // G
    dt = dt.reshape(B, c, Q, H)
    acs = _ssm_log_decay(dt, A_log).reshape(B, c, Q, G, R)
    return (x.reshape(B, c, Q, G, R, P), dt.reshape(B, c, Q, G, R), acs,
            Bm.reshape(B, c, Q, G, N), Cm.reshape(B, c, Q, G, N))


def _ssd_states(x, dt, acs, Bm):
    """Parts 2 and 3 of ``_ssd`` on ``_ssd_chunks``' arrays -> (xd = x dt
    decayed to its chunk's end, as the matmul reads it; the chunks' own
    states; the states entering them)."""
    last = acs[:, :, -1]                                  # (B, c, G, R)
    xd = (x * (dt * jnp.exp(last[:, :, None] - acs))[..., None]
          ).astype(x.dtype)
    return (xd,) + _ssm_states(Bm, xd, jnp.exp(last))


def _ssd(x, dt, A_log, Bm, Cm, chunk):
    """The state-space recurrence of Mamba-2 in its chunked SSD form (Dao &
    Gu 2024, section 6): per head, S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
    B_t, y_t = S_t C_t. x (B, T, H, P), dt (B, T, H) float32, Bm/Cm (B, T, G,
    N) -> y (B, T, H, P) float32, without the D skip.

    Four parts: inside a chunk of Q positions the masked product
    (C B^T . L) (x dt), L[l, s] the decay from s to l; the state each chunk
    builds; the recurrence over the T/Q chunk states; the entering state's
    part C S decayed to each position. dt, the cumulative log-decay, L and
    the states are float32; the matmuls read bf16 (``x.dtype``) operands and
    sum in float32. The backward pass is the compiler's transpose."""
    shape = x.shape
    with jax.named_scope(SCOPE_SSD_INCHUNK):
        x, dt, acs, Bm, Cm = _ssd_chunks(x, dt, A_log, Bm, Cm, chunk)
        Q = x.shape[2]
        # 1. inside a chunk
        a = jnp.moveaxis(acs, 2, -1)                      # (B, c, G, R, Q)
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        L = jnp.exp(jnp.where(causal, a[..., :, None] - a[..., None, :],
                              -jnp.inf))
        CB = jnp.einsum("bclgn,bcsgn->bcgls", Cm, Bm,
                        preferred_element_type=jnp.float32)
        y = jnp.einsum("bcgrls,bcsgrp->bclgrp",
                       (CB[:, :, :, None] * L).astype(x.dtype),
                       (x * dt[..., None]).astype(x.dtype),
                       preferred_element_type=jnp.float32)
    # 2. and 3. the chunks' states and the recurrence over them
    with jax.named_scope(SCOPE_SSD_STATES):
        _, _, entering = _ssd_states(x, dt, acs, Bm)
    # 4. the entering state's part
    with jax.named_scope(SCOPE_SSD_ENTER):
        y = y + jnp.exp(acs)[..., None] * jnp.einsum(
            "bclgn,bcgrpn->bclgrp", Cm, entering.astype(x.dtype),
            preferred_element_type=jnp.float32)
    return y.reshape(shape)


def _scan(x, dt, A_log, Bm, Cm, chunk, mesh=None):
    """``_ssd`` by whichever implementation serves the call: the Mosaic
    kernels of ``kernels/ssd.py`` where ``ssd_kernel.takes`` admits it (a
    TPU, one program, whole chunks of whole lane tiles), which hold a
    chunk's decay matrix, masked scores and state in VMEM and bring their
    own backward pass; the einsums of ``_ssd`` everywhere else."""
    if ssd_kernel.takes(x, Bm, chunk, mesh):
        return _ssd_kernels(x, dt, A_log, Bm, Cm, chunk)
    return _ssd(x, dt, A_log, Bm, Cm, chunk)


def _ssd_kernels(x, dt, A_log, Bm, Cm, chunk):
    """``_ssd``'s signature over the kernels, without the rule (tests and
    ``chip_smoke.py`` call it at shapes of their own). The kernels are handed
    the cumulative log-decay, so ``_ssm_log_decay`` stays the one place it is
    made; all of their time is under ``hetu_ssd_inchunk``."""
    B, T, H = dt.shape
    with jax.named_scope(SCOPE_SSD_INCHUNK):
        acs = _ssm_log_decay(dt.reshape(B, T // chunk, chunk, H), A_log)
        return ssd_kernel.ssd(x, dt, acs.reshape(B, T, H), Bm, Cm, chunk)


def _causal_conv(x, w, bias=None, activation=None):
    """A causal depthwise convolution by shifted sums: x (B, T, C), one
    weight a tap and channel ``w`` (K, C), zeros before the sequence;
    out_t = sum_j w_j x_{t-K+1+j} (+ ``bias``), then ``activation``. Summed
    in float32, whatever x is; -> (B, T, C) float32."""
    K, T = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    conv = sum(padded[:, k:k + T] * w[k] for k in range(K))
    if bias is not None:
        conv = conv + bias
    return conv if activation is None else activation(conv)


def _mamba_inputs(h, p, cfg: TransformerConfig):
    """The mixer up to its scan: [z | xBC | dt] = h W_in, xBC through the
    causal depthwise convolution, bias and SiLU -> (z (B, T, d_inner), x (B,
    T, H, P), Bm, Cm (B, T, G, N), dt_raw (B, T, H) float32 as summed)."""
    m = cfg.ssm
    B, T, _ = h.shape
    with jax.named_scope(SCOPE_SSM_PROJ):
        proj = jnp.einsum("btd,de->bte", h, p["w_in"].astype(h.dtype),
                          preferred_element_type=jnp.float32)
        z, xBC = jnp.split(proj[..., :-m.n_heads].astype(h.dtype),
                           [m.d_inner], axis=-1)
        dt_raw = proj[..., -m.n_heads:]
    with jax.named_scope(SCOPE_SSM_CONV):
        xBC = _causal_conv(xBC, p["conv_w"], p["conv_b"],
                           jax.nn.silu).astype(h.dtype)
    x, Bm, Cm = jnp.split(
        xBC, [m.d_inner, m.d_inner + m.n_groups * m.d_state], axis=-1)
    GN = (B, T, m.n_groups, m.d_state)
    return (z, x.reshape(B, T, m.n_heads, m.head_dim), Bm.reshape(GN),
            Cm.reshape(GN), dt_raw)


def _mamba(h, p, cfg: TransformerConfig, mesh, attn_bias=None):
    """The Mamba-2 mixer as HF ``GraniteMoeHybridMambaLayer`` computes it:
    [z | xBC | dt] = h W_in; xBC = SiLU(causal depthwise conv(xBC) + b);
    [x | B | C] = xBC; the recurrence (``_ssd``) + D x; RMSNorm(y SiLU(z))
    over all channels, or over each of ``cfg.ssm.norm_groups`` runs of them
    (``mamba_ssm``'s ``RMSNormGated`` at ``group_size``; Nemotron-H); W_out.
    ``attn_bias`` (a padding mask) is refused: the recurrence reads every
    position."""
    if attn_bias is not None:
        raise NotImplementedError("a mamba layer takes no attention bias")
    y = _mamba_gate_norm(_mamba_gated(h, p, cfg, mesh), p, cfg).astype(
        h.dtype)
    with jax.named_scope(SCOPE_SSM_PROJ):
        return jnp.einsum("bte,ed->btd", y, p["w_out"].astype(h.dtype),
                          preferred_element_type=jnp.float32).astype(h.dtype)


def _mamba_gated(h, p, cfg: TransformerConfig, mesh):
    """The mixer up to its gated norm -> (y + D x) SiLU(z), (B, T, d_inner)
    float32."""
    z, x, Bm, Cm, dt_raw = _mamba_inputs(h, p, cfg)
    with jax.named_scope(SCOPE_SSM_SCAN):
        y = _scan(x, _ssm_dt(dt_raw, p["dt_bias"]), p["A_log"], Bm, Cm,
                  cfg.ssm.chunk, mesh)
        with jax.named_scope(SCOPE_SSD_ENTER):
            y = y + p["D"][:, None] * x
    with jax.named_scope(SCOPE_SSM_GATE):
        return y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))


def _mamba_gate_norm(y, p, cfg: TransformerConfig):
    """The gated norm on ``_mamba_gated``'s product, float32 -> float32: over
    all channels, or by group under its own scope."""
    with jax.named_scope(SCOPE_SSM_GATE):
        if cfg.ssm.norm_groups == 1:
            return _rms_norm32(y, p["ssm_norm"], cfg.ln_eps)
        with jax.named_scope(SCOPE_SSM_GATE_NORM):
            return _rms_norm_groups(y, p["ssm_norm"], cfg.ssm.norm_groups,
                                    cfg.ln_eps)


def _short_conv(h, p, cfg: TransformerConfig, mesh, attn_bias=None):
    """The gated short convolution of LFM2 as HF ``Lfm2ShortConv.
    slow_forward`` computes it: [B | C | x] = h W_in; c = causal depthwise
    conv(B x), ``cfg.conv_width`` taps, no bias, no activation; (C c) W_out.
    The two gates and the convolution's sums are float32; the projections
    read bf16 operands. ``attn_bias`` (a padding mask) is refused: HF zeroes
    padded positions before the in-projection, which nothing here does.

    The scopes nest inside the mamba mixer's of the same part
    (``hetu_ssm_proj/hetu_sconv_proj``): a reader that knows the mixers of
    before counts this one's time where it counts theirs."""
    if attn_bias is not None:
        raise NotImplementedError("a conv layer takes no attention bias")
    chunks = _short_conv_chunks(h, p)
    with jax.named_scope(SCOPE_SSM_CONV), jax.named_scope(SCOPE_SCONV_CONV):
        y = _short_conv_gates(*chunks, p["conv_w"]).astype(h.dtype)
    with jax.named_scope(SCOPE_SSM_PROJ), jax.named_scope(SCOPE_SCONV_PROJ):
        return jnp.einsum("btd,de->bte", y, p["w_out"].astype(h.dtype),
                          preferred_element_type=jnp.float32).astype(h.dtype)


def _short_conv_chunks(h, p):
    """The in-projection's three chunks (B, C, x), each (B, T, D), in the
    compute dtype."""
    with jax.named_scope(SCOPE_SSM_PROJ), jax.named_scope(SCOPE_SCONV_PROJ):
        proj = jnp.einsum("btd,de->bte", h, p["w_in"].astype(h.dtype),
                          preferred_element_type=jnp.float32).astype(h.dtype)
    return jnp.split(proj, 3, axis=-1)


def _short_conv_gates(Bg, Cg, x, conv_w):
    """C . conv(B . x), float32: both gates and the convolution's sums."""
    z = Bg.astype(jnp.float32) * x.astype(jnp.float32)
    return Cg.astype(jnp.float32) * _causal_conv(z, conv_w)


def _kda_inputs(h, p, cfg: TransformerConfig):
    """The KDA mixer up to its scan -> (q, k (B, T, H, K), both L2-normalised
    a head, q times K^-0.5, v (B, T, H, K), the compute dtype; g (B, T, H, K)
    the log-decay a channel and beta (B, T, H), float32; the output gate's
    low-rank half (B, T, head_dim) float32)."""
    m = cfg.kda
    B, T, _ = h.shape
    R = m.head_dim
    with jax.named_scope(SCOPE_KDA_PROJ):
        qkv = jnp.einsum("btd,de->bte", h, p["kda_wqkv"].astype(h.dtype),
                         preferred_element_type=jnp.float32).astype(h.dtype)
        small = jnp.einsum(
            "btd,de->bte", h, jnp.concatenate(
                [p["kda_fa"], p["kda_ga"], p["kda_wb"]], -1).astype(h.dtype),
            preferred_element_type=jnp.float32)
        f_low, gate_low, b_raw = jnp.split(small, [R, 2 * R], axis=-1)
        # the decay's second half in float32 at full precision: it is
        # cumulated over a chunk and exponentiated
        f = jnp.einsum("btr,re->bte", f_low, p["kda_fb"],
                       precision=jax.lax.Precision.HIGHEST)
    with jax.named_scope(SCOPE_KDA_CONV):
        qkv = _causal_conv(qkv, p["kda_conv"], None, jax.nn.silu)
    heads = lambda x: x.reshape(B, T, m.n_heads, m.head_dim)
    with jax.named_scope(SCOPE_KDA_GATE):
        q, k, v = (heads(x) for x in jnp.split(qkv, 3, axis=-1))
        q = _kda_l2(q) * m.head_dim ** -0.5
        k = _kda_l2(k)
        g = _kda_log_decay(heads(f), p["kda_dt_bias"], p["kda_A_log"])
        beta = jax.nn.sigmoid(b_raw)
    return (q.astype(h.dtype), k.astype(h.dtype), v.astype(h.dtype), g, beta,
            gate_low)


def _kda_l2(x):
    """x / sqrt(sum x^2 + 1e-6) over a head's columns, float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kda_log_decay(f, dt_bias, A_log):
    """g = -exp(A_log[head]) softplus(f + dt_bias), float32: a CHANNEL's, f
    (B, T, H, K) the low-rank gate's output and ``dt_bias`` (H * K,); or a
    HEAD's (``_gdn``), f (B, T, H) and ``dt_bias`` (H,)."""
    if f.ndim == 3:
        return -jnp.exp(A_log.astype(jnp.float32)) * jax.nn.softplus(
            f.astype(jnp.float32) + dt_bias)
    H, K = f.shape[-2:]
    return -jnp.exp(A_log.astype(jnp.float32))[:, None] * jax.nn.softplus(
        f.astype(jnp.float32) + dt_bias.reshape(H, K))


def _kda_gate(gate_low, gb, cfg: TransformerConfig):
    """The output gate sigmoid((u Wga) Wgb), (B, T, columns of ``gb``)
    float32; the second matmul reads the compute dtype."""
    return jax.nn.sigmoid(jnp.einsum(
        "btr,re->bte", gate_low.astype(cfg.dtype), gb.astype(cfg.dtype),
        preferred_element_type=jnp.float32))


def _kda_gate_norm(o, gate, scale, eps):
    """RMSNorm over each head's columns of o (B, T, H, K) float32, times the
    head norm's one ``scale``, times ``gate`` (B, T, H * K): the gate AFTER
    the norm -> (B, T, H * K) float32."""
    B, T, H, K = o.shape
    normed = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * scale
    return normed.reshape(B, T, H * K) * gate


def _refuse_bias_and_cut_sequence(kind, mesh, attn_bias):
    """What a layer whose state runs through every position refuses, by the
    kind's name: a padding mask, and a mesh that cuts the sequence or the
    experts."""
    if attn_bias is not None:
        raise NotImplementedError(f"a {kind} layer takes no attention bias")
    if _axes(mesh, "sp", "ep") > 1:
        raise NotImplementedError(
            f"a {kind} layer on a mesh that cuts the sequence or the experts "
            "(sp or ep > 1): the state of a position waits for every "
            "position before it, and no exchange of states is written")


def _kda(h, p, cfg: TransformerConfig, mesh, attn_bias=None):
    """Kimi Delta Attention (``cfg.kda``): q, k, v = SiLU(conv(h W)), each
    its own convolution; q and k L2-normalised a head; a log-decay a channel
    from a low-rank gate, a step a head; the gated delta rule in its chunked
    form (``kda.scan``); RMSNorm a head, then a sigmoid gate from a second
    low-rank pair; `kda_wo`. No position signal. ``attn_bias`` (a padding
    mask) is refused: the recurrence reads every position."""
    from . import kda
    _refuse_bias_and_cut_sequence("kda", mesh, attn_bias)
    q, k, v, g, beta, gate_low = _kda_inputs(h, p, cfg)
    with jax.named_scope(SCOPE_KDA_SCAN):
        o = kda.scan(q, k, v, g, beta, cfg.kda.chunk, mesh=mesh)
    with jax.named_scope(SCOPE_KDA_GATE):
        y = _kda_gate_norm(o, _kda_gate(gate_low, p["kda_gb"], cfg),
                           p["kda_norm"], cfg.ln_eps).astype(h.dtype)
    with jax.named_scope(SCOPE_KDA_PROJ):
        return jnp.einsum("bte,ed->btd", y, p["kda_wo"].astype(h.dtype),
                          preferred_element_type=jnp.float32).astype(h.dtype)


def _gdn_inputs(h, p, cfg: TransformerConfig):
    """The Gated DeltaNet mixer up to its scan -> (q, k (B, T, Hk, K) a KEY
    head, both L2-normalised, q times K^-0.5 (key head j serves value heads
    r j .. r j + r - 1: ``kda.scan`` knows, nothing is repeated here); v (B,
    T, Hv, V); the compute dtype; g and beta (B, T, Hv) float32, the
    log-decay a HEAD; z (B, T, Hv * V) the output gate's argument, the
    compute dtype)."""
    m = cfg.gdn
    B, T, _ = h.shape
    with jax.named_scope(SCOPE_GDN_PROJ):
        qkvz = jnp.einsum("btd,de->bte", h, p["gdn_wqkvz"].astype(h.dtype),
                          preferred_element_type=jnp.float32).astype(h.dtype)
        # the decay's and the step's logits stay float32 as summed: g is
        # cumulated over a chunk and exponentiated
        ba = jnp.einsum("btd,de->bte", h, p["gdn_wba"].astype(h.dtype),
                        preferred_element_type=jnp.float32)
    qkv, z = jnp.split(qkvz, [m.conv_dim], axis=-1)
    with jax.named_scope(SCOPE_GDN_CONV):
        qkv = _causal_conv(qkv, p["gdn_conv"], None, jax.nn.silu)
    with jax.named_scope(SCOPE_GDN_GATE):
        q, k, v = jnp.split(qkv, [m.qk_inner, 2 * m.qk_inner], axis=-1)
        q, k = (x.reshape(B, T, m.n_k_heads, m.k_dim) for x in (q, k))
        q = (_kda_l2(q) * m.k_dim ** -0.5).astype(h.dtype)
        k = _kda_l2(k).astype(h.dtype)
        v = v.reshape(B, T, m.n_v_heads, m.v_dim).astype(h.dtype)
        b_raw, a_raw = jnp.split(ba, 2, axis=-1)
        g = _kda_log_decay(a_raw, p["gdn_dt_bias"], p["gdn_A_log"])
        beta = jax.nn.sigmoid(b_raw)
    return q, k, v, g, beta, z


def _gdn_scan(q, k, v, g, beta, cfg: TransformerConfig, mesh=None,
              terms=False):
    """``kda.scan`` with the decay a head's and q, k a key head, under the
    mixer's own scope name."""
    from . import kda
    return kda.scan(q, k, v, g, beta, cfg.gdn.chunk, terms=terms, mesh=mesh,
                    scope=SCOPE_GDN_SCAN)


def _gdn(h, p, cfg: TransformerConfig, mesh, attn_bias=None):
    """A Gated DeltaNet mixer (``cfg.gdn``): [q | k | v | z] = h W_qkvz, [b |
    a] = h W_ba; [q | k | v] through ONE causal convolution and SiLU; q and k
    L2-normalised a key head, each key head serving its value heads; ONE
    log-decay a value head (``_kda_log_decay``), a step a head; the gated
    delta rule in its chunked form (``_gdn_scan``); RMSNorm a head, THEN
    SiLU(z) (``_kda_gate_norm``: the order of norm and gate is the kind's);
    `gdn_wo`. No position signal. ``attn_bias`` (a padding mask) is refused:
    the recurrence reads every position."""
    _refuse_bias_and_cut_sequence("gdn", mesh, attn_bias)
    q, k, v, g, beta, z = _gdn_inputs(h, p, cfg)
    with jax.named_scope(SCOPE_GDN_SCAN):
        o = _gdn_scan(q, k, v, g, beta, cfg, mesh)
    with jax.named_scope(SCOPE_GDN_GATE):
        y = _kda_gate_norm(o, jax.nn.silu(z.astype(jnp.float32)),
                           p["gdn_norm"], cfg.ln_eps).astype(h.dtype)
    with jax.named_scope(SCOPE_GDN_PROJ):
        return jnp.einsum("bte,ed->btd", y, p["gdn_wo"].astype(h.dtype),
                          preferred_element_type=jnp.float32).astype(h.dtype)


@dataclasses.dataclass(frozen=True)
class _Kind:
    """What a kind of mixer brings: its stacked weights, their
    PartitionSpecs and the mixer ``(h, layer_params, cfg, mesh, attn_bias)
    -> (B, T, D)``; ``_block`` is the one body around it. ``side_loss``: the
    mixer returns (output, a float32 scalar) and the scalar is a loss of the
    layer's own, which ``_block`` carries out beside the router's two."""
    init: Any
    specs: Any
    mixer: Any
    side_loss: bool = False


_KINDS = {"attention": _Kind(_init_attention, _attention_specs, _attention),
          "mamba": _Kind(_init_mamba, _mamba_specs, _mamba),
          "conv": _Kind(_init_short_conv, _short_conv_specs, _short_conv),
          "mla": _Kind(_init_mla, _mla_specs, _mla),
          "dsa": _Kind(_init_dsa, _dsa_specs, _dsa, side_loss=True),
          "window": _Kind(_init_window, _window_specs, _window),
          "kda": _Kind(_init_kda, _kda_specs, _kda),
          "gdn": _Kind(_init_gdn, _gdn_specs, _gdn),
          # no mixer: a single sublayer that is its MLP half (``_block``)
          "mlp": _Kind(lambda ks, cfg, n: {}, lambda cfg: {}, None)}


def _dense_mlp(h, p, cfg, mesh):
    if cfg.mlp in GATED_MLPS:
        # Llama MLP: down(silu(gate(x)) * up(x)); the b1/b2 params exist
        # but are zero/unused in this dialect (no biases in the family).
        # "reglu": relu for silu, nothing else
        act = jax.nn.silu if cfg.mlp == "swiglu" else jax.nn.relu
        with jax.named_scope(SCOPE_BLK_MLP_UP):
            gate = jnp.einsum("btd,df->btf", h, p["w1"].astype(h.dtype),
                              preferred_element_type=jnp.float32)
            up = jnp.einsum("btd,df->btf", h, p["w3"].astype(h.dtype),
                            preferred_element_type=jnp.float32)
            u = (act(gate) * up).astype(h.dtype)
        with jax.named_scope(SCOPE_BLK_MLP_DOWN):
            return jnp.einsum(
                "btf,fd->btd", u, p["w2"].astype(h.dtype),
                preferred_element_type=jnp.float32).astype(h.dtype)
    if cfg.mlp == "relu2":
        # Nemotron-H's MLP: down(relu(up(x))^2), no gate and no bias
        with jax.named_scope(SCOPE_BLK_MLP_UP):
            u = _relu2(jnp.einsum("btd,df->btf", h, p["w1"].astype(h.dtype),
                                  preferred_element_type=jnp.float32)
                       ).astype(h.dtype)
        with jax.named_scope(SCOPE_BLK_MLP_DOWN):
            return jnp.einsum(
                "btf,fd->btd", u, p["w2"].astype(h.dtype),
                preferred_element_type=jnp.float32).astype(h.dtype)
    with jax.named_scope(SCOPE_BLK_MLP_UP):
        u = jnp.einsum("btd,df->btf", h, p["w1"].astype(h.dtype),
                       preferred_element_type=jnp.float32).astype(h.dtype)
        u = _gelu(u + p["b1"].astype(h.dtype), cfg)
    with jax.named_scope(SCOPE_BLK_MLP_DOWN):
        out = jnp.einsum("btf,fd->btd", u, p["w2"].astype(h.dtype),
                         preferred_element_type=jnp.float32).astype(h.dtype)
        return out + p["b2"].astype(h.dtype)


@jax.custom_vjp
def _noting_picks(top_p, bias, counts):
    """``top_p`` as it is. The selection bias has no gradient, so its
    cotangent is DEFINED to be ``counts``, the picks each expert took: the
    step's backward pass hands ``make_train_step`` what the bias rule needs
    from the forward pass that was differentiated (under ``remat``: its
    recomputation), and no second pass nor a second output of the loss
    exists. ``top_p`` carries it because the loss reads it."""
    return top_p


def _noting_picks_fwd(top_p, bias, counts):
    return top_p, counts


def _noting_picks_bwd(counts, g):
    return g, counts.astype(jnp.float32), None


_noting_picks.defvjp(_noting_picks_fwd, _noting_picks_bwd)


def _route(x, p, cfg: TransformerConfig):
    """The router of one MoE block (its layer's params ``p``) on token rows
    ``x`` (S, D), in float32 whatever the compute dtype -> (top_p (S, k) the
    picks' weights, top_e (S, k) int32, counts (E,) picks an expert, probs
    (S, E) the scores, aux (2,) = [balance, z]), E the router's width. The
    form is ``cfg.router``'s:

    - scores: softmax over the experts (OLMoE), or sigmoid an expert (LFM2);
    - the picks: the k largest scores; with ``router.bias`` the k largest of
      score + ``p[ROUTER_BIAS]``, the bias entering nowhere else;
    - weights: the picks' scores as they stand (OLMoE: UNnormalised softmax
      probabilities), or over (their sum + ``router.normalize_eps``)
      (``router.normalize``), times ``router.scale``.

    balance = E * sum_e f_e P_e with f_e the picks of expert e over tokens
    (they sum to k) and P_e its mean probability: Switch Transformer eq. 4
    at k = 1, HF ``load_balancing_loss_func`` otherwise. z = mean over
    tokens of logsumexp(logits)^2 (ST-MoE). ``loss_fn`` weights them; zeros
    where ``router.aux_losses`` is off."""
    r, k = cfg.router, cfg.n_experts_per_tok
    E = r.width or cfg.n_experts
    logits = jnp.einsum("sd,de->se", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = _router_scores(logits, r.score)
    if r.bias:
        _, top_e = jax.lax.top_k(_router_select(probs, p[ROUTER_BIAS]), k)
        top_p = jnp.take_along_axis(probs, top_e, -1)
    else:
        top_p, top_e = jax.lax.top_k(probs, k)
    counts = jnp.sum(top_e[..., None] == jnp.arange(E), axis=(0, 1))
    if r.bias:
        top_p = _noting_picks(top_p, p[ROUTER_BIAS], counts)
    if r.normalize:
        top_p = _router_normalize(top_p, r.normalize_eps)
    if r.scale != 1.0:
        top_p = top_p * r.scale
    if not r.aux_losses:
        return top_p, top_e, counts, probs, jnp.zeros((2,), jnp.float32)
    balance = E * jnp.sum(counts / x.shape[0] * jnp.mean(probs, 0))
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return top_p, top_e, counts, probs, jnp.stack([balance, z])


def _router_scores(logits, score):
    """The experts' scores from the router's float32 logits."""
    return (jax.nn.softmax(logits, -1) if score == "softmax"
            else jax.nn.sigmoid(logits))


def _router_select(scores, bias):
    """What the picks are the k largest of, float32."""
    return scores + bias.astype(jnp.float32)


def _router_normalize(top_p, eps=1e-6):
    """The picks' weights over their sum, float32."""
    return top_p / (jnp.sum(top_p, -1, keepdims=True) + eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(x, order, inv, k):
    """Token rows (S, D) -> pick rows (S*k, D) in sorted order: pick
    ``order[i]`` belongs to token ``order[i] // k``. ``inv`` is ``order``'s
    inverse: the cotangent is a gather and a sum over the k picks, where
    autodiff would emit a scatter-add of S*k rows."""
    return x[order // k]


def _dispatch_rows_fwd(x, order, inv, k):
    return x[order // k], inv


def _dispatch_rows_bwd(k, inv, g):
    S = g.shape[0] // k
    picks = g[inv].reshape(S, k, -1).astype(jnp.float32)
    return jnp.sum(picks, 1).astype(g.dtype), None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _permute_rows(x, perm, inv):
    """``x[perm]`` for a permutation and its inverse: cotangent ``g[inv]``."""
    return x[perm]


def _permute_rows_fwd(x, perm, inv):
    return x[perm], inv


def _permute_rows_bwd(inv, g):
    return g[inv], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _grouped_matmul(xs, w, group_sizes, mesh=None):
    """Rows sorted by group (M, K) x one matrix a group (E, K, N) -> (M, N):
    ``jax.lax.ragged_dot``, which the TPU compiler lowers to its own grouped
    matmul kernel (M*K*N multiply-adds, not E times that) and differentiates
    into two more (PERF.md has its share of the roofline). That kernel walks
    512-row tiles and never holds a weight block whole: on a TPU and in one
    program the Pallas kernels of ``kernels/grouped_matmul.py`` serve the
    call at every width, with tiles made from the widths
    (``gmm_kernel.takes`` is the rule)."""
    return kernel_registry.dispatch(gmm_kernel.GROUPED_MATMUL, xs,
                                    w.astype(xs.dtype), group_sizes, mesh=mesh)


def _swiglu(gate, up):
    """silu(gate) * up, in float32, at ``gate``'s dtype."""
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def _reglu(gate, up):
    """relu(gate) * up, in float32, at ``gate``'s dtype."""
    return (jax.nn.relu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def _relu2(u):
    """relu(u)^2, in float32, at ``u``'s dtype."""
    return jnp.square(jax.nn.relu(u.astype(jnp.float32))).astype(u.dtype)


# -- a share's row loops ----------------------------------------------------------
# On a share (``Router.width``) the held picks sort FIRST, so the rows that
# matter are [0, n) of the sorted order, n = the held experts' picks, a
# number on the device. Every pass around the grouped matmuls cuts the rows
# into chunks of R and runs ``ceil(n / R)`` of them, a run-time trip count:
# no host read, one program at any load, exact at any load (all chunks with
# every pick here, none with none). Rows past the last chunk run are never
# written and never read: the grouped matmuls read their groups only.

# bytes of (., d_model) rows in the compute dtype a chunk covers: 1,024 rows
# of 4 KB. On the v5e a loop step costs what its rows cost (512 to 4,096 rows
# a step gather alike, PERF.md PR 38), and a chunk more in a layer ~0.4 ms
# over all passes, 0.05 % of the lfm2 cell's step and less than a tenth of
# what a point of held share does: where a load's edge falls among the
# chunks does not show in the step time
_ROW_CHUNK_BYTES = 4 << 20


def _row_chunk(S, D, dtype):
    """Rows a chunk of a share's loops covers, from the shapes alone: what
    ``_ROW_CHUNK_BYTES`` hold of (., D) rows, as a power of two that divides
    S (and so S*k); S whole where S has no such divisor of 8 or more."""
    want = max(8, _ROW_CHUNK_BYTES // (D * jnp.dtype(dtype).itemsize))
    R = math.gcd(S, 1 << (want.bit_length() - 1))
    return R if R >= 8 else S


def _rows_run(n, R, N):
    """Rows the loops cover when ``n`` of N matter: whole chunks of R. The
    loops' trip count is this over R, and ``moe_routing_stats`` reports it."""
    return jnp.minimum(-(-n // R) * R, N)


def _loop_rows(rows, R, body, init):
    """``carry = body(start, carry)`` for start = 0, R, ... below ``rows``
    (a run-time multiple of R)."""
    return jax.lax.fori_loop(0, rows // R,
                             lambda c, carry: body(c * R, carry), init)


def _cut(a, start, R):
    return jax.lax.dynamic_slice_in_dim(a, start, R)


def _put(buf, rows, start):
    return jax.lax.dynamic_update_slice_in_dim(buf, rows, start, 0)


def _share_plan(order, inv, held, counts, R):
    """What a share's loops read beside their rows, from the routing alone
    (integers; no gradient): ``order`` / ``inv`` / ``held`` as given;
    ``rows_run`` the sorted rows to cover. For the passes from sorted rows
    back to tokens (gathers only, no scatter): ``into`` (S, k, k) puts a
    token's held picks into its first slots, ``by_held`` orders tokens by
    how many picks they hold, most first, ``rank`` undoes it, ``rows``
    (S, k) the sorted row of slot j of token ``by_held[i]``, ``n_held`` (S,)
    theirs in that order, ``tokens_run`` the tokens that hold any. Slot j
    then is a PREFIX of that order, so its rows are gathered in chunks while
    tokens remain, sum over j = the held rows, and one gather of S rows
    returns the sums to token order."""
    S, k = held.shape
    slot = jnp.cumsum(held, -1) - 1
    into = held[:, :, None] & (slot[:, :, None] == jnp.arange(k))
    rows = jnp.sum(jnp.where(into, inv.reshape(S, k, 1), 0), 1)
    # ONE sort carries the tokens' rows along: no gather of S small rows
    fewest_last, by_held, *rows = jax.lax.sort(
        (-jnp.sum(held, -1, dtype=jnp.int32), jnp.arange(S, dtype=jnp.int32))
        + tuple(rows[:, j] for j in range(k)), num_keys=1, is_stable=True)
    return {"order": order, "inv": inv, "held": held,
            "rows_run": _rows_run(jnp.sum(counts), R, S * k),
            "into": into, "by_held": by_held,
            "rank": jnp.argsort(by_held).astype(jnp.int32),
            "rows": jnp.stack(rows, -1), "n_held": -fewest_last,
            "tokens_run": _rows_run(jnp.sum(fewest_last < 0), R, S)}


def _rows_to_tokens(src, plan, weights, R):
    """Sorted rows ``src`` (S*k, D) -> (S, D): a token's sum over its held
    picks of (``weights`` (S, k), by pick, times) the pick's row, summed in
    float32. The tokens' order and slots are ``_share_plan``'s."""
    rows, n_held = plan["rows"], plan["n_held"]
    (S, k), dtype = rows.shape, src.dtype
    if weights is not None:
        weights = jnp.sum(jnp.where(plan["into"], weights[:, :, None], 0.0),
                          1)[plan["by_held"]]

    def body(start, buf):
        r, n = _cut(rows, start, R), _cut(n_held, start, R)
        w = None if weights is None else _cut(weights, start, R)

        def add(acc, j):
            y = src[r[:, j]].astype(jnp.float32)
            if w is not None:
                y = y * w[:, j, None]
            return acc + jnp.where((n > j)[:, None], y, 0.0)

        acc = add(jnp.zeros((R, src.shape[1]), jnp.float32), 0)
        for j in range(1, k):       # tokens hold fewer picks further on
            acc = jax.lax.cond(n[0] > j, functools.partial(add, j=j),
                               lambda acc: acc, acc)
        return _put(buf, acc.astype(dtype), start)

    buf = _loop_rows(plan["tokens_run"], R, body,
                     jax.lax.empty((S, src.shape[1]), dtype))
    return jnp.where(jnp.any(plan["held"], -1, keepdims=True),
                     buf[plan["rank"]], jnp.zeros((), dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _share_dispatch(x, plan, R):
    """``_dispatch_rows`` on a share: rows [0, ``plan["rows_run"]``) of the
    sorted order are gathered, a chunk a step; the rest is not written. The
    cotangent sums a token's HELD picks' rows (``_rows_to_tokens``)."""
    order, k = plan["order"], plan["held"].shape[1]

    def body(start, xs):
        return _put(xs, x[_cut(order, start, R) // k], start)

    return _loop_rows(plan["rows_run"], R, body,
                      jax.lax.empty((order.shape[0], x.shape[1]), x.dtype))


def _share_dispatch_fwd(x, plan, R):
    return _share_dispatch(x, plan, R), plan


def _share_dispatch_bwd(R, plan, g):
    return _rows_to_tokens(g, plan, None, R), None


_share_dispatch.defvjp(_share_dispatch_fwd, _share_dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_twice(xs, rows, R):
    """``xs`` for its two readers (the gate's and the up projection's
    grouped matmul): their cotangents are summed on the first ``rows`` rows,
    a chunk a step, where autodiff would add all of both arrays."""
    return xs, xs


def _rows_twice_fwd(xs, rows, R):
    return (xs, xs), rows


def _rows_twice_bwd(R, rows, g):
    def body(start, acc):        # the sum written over the first
        return _put(acc, _cut(acc, start, R) + _cut(g[1], start, R), start)

    return _loop_rows(rows, R, body, g[0]), None


_rows_twice.defvjp(_rows_twice_fwd, _rows_twice_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _act_rows(act, operands, rows, R):
    """The experts' activation ``act`` (``_swiglu`` or ``_reglu`` of (gate,
    up), ``_relu2`` of (u,)) on the first ``rows`` rows of its ``operands``,
    a chunk a step."""
    def body(start, out):
        return _put(out, act(*(_cut(a, start, R) for a in operands)), start)

    return _loop_rows(rows, R, body, jax.lax.empty(operands[0].shape,
                                                   operands[0].dtype))


def _act_rows_fwd(act, operands, rows, R):
    return _act_rows(act, operands, rows, R), (operands, rows)


def _act_rows_bwd(act, R, res, g):
    operands, rows = res

    def body(start, grads):      # written over the operands, a chunk read
        _, pull = jax.vjp(act, *(_cut(a, start, R) for a in grads))
        return tuple(_put(a, d, start) for a, d in
                     zip(grads, pull(_cut(g, start, R))))

    return _loop_rows(rows, R, body, operands), None


_act_rows.defvjp(_act_rows_fwd, _act_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _share_combine(ys, top_p, plan, R):
    """The experts' rows ``ys`` (S*k, D), sorted -> (S, D): a token's held
    picks' rows weighted by ``top_p`` (S, k) and summed in float32. The
    cotangent needs ``ys`` and nothing of this pass: a chunk of sorted rows
    a step, it gathers the tokens' cotangent rows ONCE for both the rows'
    (weight . g[token]) and the weights' (<row, g[token]>)."""
    return _rows_to_tokens(ys, plan, top_p, R)


def _share_combine_fwd(ys, top_p, plan, R):
    return _share_combine(ys, top_p, plan, R), (ys, top_p, plan)


def _share_combine_bwd(R, res, g):
    ys, top_p, plan = res
    order, held = plan["order"], plan["held"]
    weights, k = top_p.reshape(-1), held.shape[1]

    def body(start, carry):      # the rows' cotangent written over the rows
        ys, dots = carry
        picks = _cut(order, start, R)
        of_token = g[picks // k].astype(jnp.float32)
        d = of_token * weights[picks][:, None]
        dot = jnp.sum(_cut(ys, start, R).astype(jnp.float32) * of_token, -1)
        return _put(ys, d.astype(ys.dtype), start), _put(dots, dot, start)

    d_ys, dots = _loop_rows(plan["rows_run"], R, body,
                            (ys, jnp.zeros(ys.shape[:1], jnp.float32)))
    d_p = jnp.where(held, dots[plan["inv"]].reshape(held.shape), 0.0)
    return d_ys, d_p.astype(top_p.dtype), None


_share_combine.defvjp(_share_combine_fwd, _share_combine_bwd)


def _moe_mlp(h, p, cfg: TransformerConfig, mesh, routing=None):
    """An expert layer's MLP half -> (out, aux (2,)): the routed picks
    (``_routed_experts``) and, under ``cfg.d_ff_shared``, the shared expert
    beside them: ONE MLP of the experts' form (SwiGLU, or relu2's two
    matrices ``ws1`` / ``ws2``) on every token, added to the routed sum as
    HF ``DeepseekV3MoE.forward`` adds it. It is no expert of the router's:
    a share (``cfg.router.width``) computes it whole, once, whatever it
    holds, so the parts of the members of a group add up to the layer only
    with the shared expert counted once. ``routing``: the layer's routing
    where ``_block`` made it ahead of the mixer (``_plan_routing``)."""
    out, aux = _routed_experts(h, p, cfg, mesh, routing)
    if cfg.d_ff_shared:
        with jax.named_scope(SCOPE_MOE_SHARED):
            shared = {"w1": p["ws1"], "w2": p["ws2"]}
            if "ws3" in p:      # the gated form's third matrix
                shared["w3"] = p["ws3"]
            y = _dense_mlp(h, shared, cfg, mesh)
            if cfg.shared_gate:     # ONE gate logit a token, float32
                gate = jax.nn.sigmoid(jnp.einsum(
                    "btd,do->bto", h, p["wsg"].astype(h.dtype),
                    preferred_element_type=jnp.float32))
                y = (y.astype(jnp.float32) * gate).astype(y.dtype)
            out = out + y
    return out, aux


def _plan_routing(x, p, cfg: TransformerConfig):
    """Everything an expert layer takes from its ROUTER, from the rows ``x``
    (S, D) the router reads (``cfg.router.input``: the MLP half's normed
    input, or the layer's own input) and nothing else, so it can be made
    wherever those rows exist: ``top_p`` (S, k) the picks' weights (with the
    router's gradient), ``flat_e`` (S*k,) the picks' experts as the sort
    reads them, ``order`` / ``inv`` the stable sort by expert and its
    inverse, ``group_sizes`` the held experts' rows, ``aux`` (2,); on a share
    also ``R`` and ``plan`` (``_share_plan``)."""
    first, E = cfg.router.first_held, cfg.n_experts
    share = (cfg.router.width or E) != E
    top_p, top_e, counts, _, aux = _route(x, p, cfg)
    flat_e = top_e.reshape(-1)
    if share:
        held = (top_e >= first) & (top_e < first + E)
        # held picks first, by expert; the rest behind them
        flat_e = jnp.where(held.reshape(-1), flat_e - first, E)
        counts = counts[first:first + E]
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    routing = {"top_p": top_p, "flat_e": flat_e, "order": order, "inv": inv,
               "group_sizes": counts,  # picks an expert = rows of its group
               "aux": aux}
    if share:
        R = _row_chunk(x.shape[0], x.shape[1], x.dtype)
        routing.update(R=R, plan=_share_plan(order, inv, held, counts, R))
    return routing


def _routed_experts(h, p, cfg: TransformerConfig, mesh, routing=None):
    """Dropless top-k MoE: every pick on an expert held here is computed.
    The S*k picks are sorted by expert (stable), token rows gathered in that
    order, each projection is one grouped matmul over the uneven groups, and
    the results return to token order weighted as the router's form says
    (``_route``: OLMoE's unnormalised softmax probabilities, LFM2's sigmoid
    scores normalised over the k picks). -> (out, aux (2,)). k = 1 is the
    same code.

    The share (``cfg.router.width`` > ``cfg.n_experts``): the router scores
    and picks among all ``width`` experts and the weights are those of all k
    picks; ``p`` holds experts [first_held, first_held + n_experts) and the
    sum runs over the picks that land there. What the absent experts would
    have added is left out: the result is this chip's PART of the layer's.
    Held picks sort first, by expert, the others after them, in no group:
    the grouped matmuls get the held experts' group sizes and spend no row
    on the rest. The arrays keep S*k rows, the worst case (every pick here),
    so no imbalance can drop a pick, but only the rows HELD are worked on:
    dispatch, the sum of the two projections' cotangents, activation and
    combine, forward and backward, loop over chunks of ``_row_chunk`` rows
    to a bound the routing sets on the device ("a share's row loops" above;
    one ``ragged_dot`` a projection still covers the whole array and spends
    its groups). Past that bound a row is never written and never read.
    Without a share the bound would be S*k and the passes are the
    whole-array gathers they were: two implementations until ROADMAP
    S15(e) runs every config through the loops.

    Under a mesh with ``ep > 1`` the older top-1 capacity form runs instead
    (``_moe_mlp_capacity``): experts over ``ep`` by all-to-all for this
    form is ROADMAP R1's remaining item, and a share there is refused, as is
    a router that reads the layer's input.

    ``routing``: ``_plan_routing``'s, made by ``_block`` ahead of the mixer
    where the router reads the layer's input (``cfg.router.input``
    "block"); made here, from ``h``'s rows, otherwise."""
    E = cfg.n_experts
    share = (cfg.router.width or E) != E
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        if share or routing is not None:
            raise MoEConfigError(
                f"a share of an expert layer ({E} of {cfg.router.width} "
                "experts held), or a router on the layer's input "
                f"(router.input={cfg.router.input!r}), on a mesh with "
                f"ep={mesh.shape['ep']}: the share is what ONE member of an "
                "expert-parallel group computes, and early routing's "
                "exchange of counts behind the mixer is part of the same "
                "exchange over `ep`, which is not written")
        return _moe_mlp_capacity(h, p, cfg, mesh)
    B, T, D = h.shape
    k = cfg.n_experts_per_tok
    x = h.reshape(B * T, D)
    if routing is None:
        with jax.named_scope(SCOPE_MOE_ROUTE):
            routing = _plan_routing(x, p, cfg)
    top_p, flat_e, order, inv, group_sizes, aux = (routing[n] for n in (
        "top_p", "flat_e", "order", "inv", "group_sizes", "aux"))
    if share:
        R, plan = routing["R"], routing["plan"]
    with jax.named_scope(SCOPE_MOE_DISPATCH):
        xs = (_share_dispatch(x, plan, R) if share
              else _dispatch_rows(x, order, inv, k))         # (S*k, D)
    with jax.named_scope(SCOPE_MOE_EXPERTS):
        if cfg.mlp in GATED_MLPS:
            xs, xs_up = (_rows_twice(xs, plan["rows_run"], R) if share
                         else (xs, xs))
            u = _grouped_matmul(xs, p["w1"], group_sizes, mesh)
            up = _grouped_matmul(xs_up, p["w3"], group_sizes, mesh)
            if cfg.mlp == "swiglu":     # unnamed, as it lowered
                u = (_act_rows(_swiglu, (u, up), plan["rows_run"], R)
                     if share else _swiglu(u, up))
            else:
                with jax.named_scope(SCOPE_MOE_ACT):
                    u = (_act_rows(_reglu, (u, up), plan["rows_run"], R)
                         if share else _reglu(u, up))
            ys = _grouped_matmul(u, p["w2"], group_sizes, mesh)
        elif cfg.mlp == "relu2":    # ungated, no bias: the held rows alone
            u = _grouped_matmul(xs, p["w1"], group_sizes, mesh)
            with jax.named_scope(SCOPE_MOE_ACT):
                u = (_act_rows(_relu2, (u,), plan["rows_run"], R) if share
                     else _relu2(u))
            ys = _grouped_matmul(u, p["w2"], group_sizes, mesh)
        else:   # biased GELU experts: over every row, on a share too
            u = _grouped_matmul(xs, p["w1"], group_sizes, mesh)
            sorted_e = flat_e[order]
            b1, b2 = p["b1"], p["b2"]
            if share:
                # a row past the groups has no expert (``sorted_e`` is E)
                # and takes a bias row of zeros: the biases' gradient is a
                # sum over ALL rows, and what the loops left past the groups,
                # values or cotangents, lands in the row that is cut off
                b1, b2 = (jnp.pad(b, ((0, 1), (0, 0))) for b in (b1, b2))
            b1, b2 = (b.astype(x.dtype)[sorted_e] for b in (b1, b2))
            u = _gelu(u + b1, cfg)
            ys = _grouped_matmul(u, p["w2"], group_sizes, mesh) + b2
    with jax.named_scope(SCOPE_MOE_COMBINE):
        if share:
            out = _share_combine(ys, top_p, plan, R)
        else:
            y = _permute_rows(ys, inv, order).reshape(B * T, k, D)
            out = jnp.sum(y.astype(jnp.float32) * top_p[..., None], 1)
    return out.astype(h.dtype).reshape(B, T, D), aux


def _moe_mlp_capacity(h, p, cfg: TransformerConfig, mesh):
    """Switch-style top-1 MoE with a capacity that DROPS tokens, kept for
    ``ep > 1`` meshes only (experts sharded over ep; the dispatch/combine
    einsums become all-to-alls under GSPMD). Its one-hot (S, E, cap)
    dispatch tensor does not scale to many experts or picks."""
    if cfg.n_experts_per_tok != 1:
        raise MoEConfigError(
            f"n_experts_per_tok={cfg.n_experts_per_tok} on a mesh with "
            f"ep={mesh.shape['ep']}: the expert-parallel path is top-1 with "
            "a capacity; run top-k experts without an ep axis")
    B, T, D = h.shape
    E = cfg.n_experts
    S = B * T
    cap = max(1, int(cfg.capacity_factor * S / E))
    x = h.reshape(S, D)
    top_p, top_e, _, _, aux = _route(x, p, cfg)
    gate, expert = top_p[:, 0], top_e[:, 0]
    # position of each token within its expert's capacity buffer
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot
    pos = jnp.max(pos_in_expert, axis=-1) - 1          # (S,)
    keep = pos < cap
    dispatch = (jax.nn.one_hot(expert, E, dtype=x.dtype)[:, :, None] *
                jax.nn.one_hot(pos, cap, dtype=x.dtype)[:, None, :] *
                keep[:, None, None].astype(x.dtype))    # (S, E, cap)
    expert_in = jnp.einsum("sec,sd->ecd", dispatch, x)  # (E, cap, D)
    expert_in = _constrain(expert_in, mesh, "ep", None, None)
    u = jnp.einsum("ecd,edf->ecf", expert_in, p["w1"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    if cfg.mlp in GATED_MLPS:
        up = jnp.einsum("ecd,edf->ecf", expert_in, p["w3"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
        act = jax.nn.silu if cfg.mlp == "swiglu" else jax.nn.relu
        u = (act(u) * up).astype(x.dtype)
        y = jnp.einsum("ecf,efd->ecd", u, p["w2"].astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    else:
        u = _gelu(u.astype(x.dtype) + p["b1"][:, None, :].astype(x.dtype),
                  cfg)
        y = jnp.einsum("ecf,efd->ecd", u, p["w2"].astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
        y = y + p["b2"][:, None, :].astype(x.dtype)
    combine = dispatch * gate[:, None, None].astype(x.dtype)
    out = jnp.einsum("sec,ecd->sd", combine, y)
    return out.reshape(B, T, D), aux


def _aux_size(cfg: TransformerConfig):
    """Entries of a block's aux: the router's [balance, z], and in a stack
    with learned sparse attention the indexer's loss after them."""
    return 3 if "dsa" in cfg.layer_types else 2


def _residual(out, cfg: TransformerConfig):
    """A sublayer's output as its residual add takes it."""
    r = cfg.multipliers.residual
    # in float32: bf16(0.22) is 0.1 % off, the same way every layer
    return out if r == 1.0 else (out.astype(jnp.float32) * r).astype(
        out.dtype)


def _router_rows(h):
    """The stream (B, T, D) as the rows (S, D) a router reads, AS ROUNDED to
    the compute dtype: behind the barrier the compiler cannot feed the
    router's float32 matmul an unrounded producer's output (its excess
    precision; PERF.md, PR 55), so a check that recomputes the picks from
    these rows can explain every one."""
    return jax.lax.optimization_barrier(h.reshape(-1, h.shape[-1]))


def _block_attn(h, layer_params, cfg: TransformerConfig, mesh, attn_bias,
                dropout_rng, kind="attention"):
    """The mixer half of ``_block`` (attention, or what ``kind``'s mixer
    names in ``_KINDS``) -> (h after the residual, the MLP half's input)."""
    return _block_mixer(h, layer_params, cfg, mesh, attn_bias, dropout_rng,
                        kind)[:2]


def _block_mixer(h, layer_params, cfg: TransformerConfig, mesh, attn_bias,
                 dropout_rng, kind):
    """``_block_attn`` and, third, the mixer's own loss (None without:
    ``_Kind.side_loss``). A single sublayer (``cfg.single_sublayer``): kind
    "mlp" has no mixer and h passes to the MLP half's norm as it came; a
    kind without an MLP half (``has_mlp``) has no such input, None."""
    post = cfg.post_ln
    mixer = _KINDS[mixer_of(kind)]
    h = _constrain(h, mesh, "dp", "sp", None)
    side = None
    if mixer.mixer is not None:
        attn_in = h if post else _norm(
            h, layer_params["ln1_scale"], layer_params["ln1_bias"], cfg)
        attn_out = mixer.mixer(attn_in, layer_params, cfg, mesh, attn_bias)
        if mixer.side_loss:
            attn_out, side = attn_out
        if cfg.sandwich_norm:
            # the norm's backward pass reads its input: kept, the mixer's
            # last matmul (`wo`) is not run again for it
            attn_out = _norm(checkpoint_name(attn_out, REMAT_NORM1_IN),
                             layer_params["ln1_post_scale"],
                             layer_params["ln1_post_bias"], cfg)
        attn_out = _dropout(_residual(attn_out, cfg), cfg.dropout_rate,
                            dropout_rng)
        h = checkpoint_name(h + attn_out, REMAT_X1)
        if post:
            h = _norm(h, layer_params["ln1_scale"],
                      layer_params["ln1_bias"], cfg)
        h = _constrain(h, mesh, "dp", "sp", None)
    if not has_mlp(kind):
        return h, None, side
    mlp_in = h if post else _norm(
        h, layer_params["ln2_scale"], layer_params["ln2_bias"], cfg)
    return h, mlp_in, side


def _block(h, layer_params, cfg: TransformerConfig, mesh, attn_bias=None,
           dropout_rng=None, kind="attention"):
    """One block of any kind -> (h, aux (2,) = the MoE block's [balance,
    z] losses, zeros for a dense MLP; in a stack with "dsa" layers (3,), the
    third the indexer's loss of the layer, ``_aux_size``). Pre-LN (flagship default): LN ->
    sublayer -> residual. Post-LN (``cfg.post_ln``, canonical BERT /
    original Transformer): sublayer -> residual -> LN, with ln1 after
    attention and ln2 after the MLP. Sandwich (``cfg.sandwich_norm``,
    Ouro): LN -> sublayer -> LN -> residual. The first sublayer is
    ``kind``'s mixer (``_KINDS``: attention, a Mamba-2 mixer, a gated short
    convolution), the second its MLP half (``experts_of``: the experts, or
    the dense MLP); each sublayer's output takes
    ``cfg.multipliers.residual`` before its add. A stack of single
    sublayers (``cfg.single_sublayer``) runs ONE of the two a layer, with
    its one norm: the mixer (``has_mlp`` false), or the MLP half (kind
    "mlp").

    LOCKSTEP CONTRACT: any new dialect knob added here must be mirrored
    in ``generate._decode_layer`` (the KV-cache form of this block) or
    decode silently diverges from training for that config."""
    k1, k2 = (None, None) if dropout_rng is None else jax.random.split(
        dropout_rng)
    routing = None
    if experts_of(cfg, kind) > 0 and cfg.router.input == "block":
        # the router reads the stream as it ENTERS the layer: the routing
        # (picks, weights, counts, the sort, a share's plan) waits for
        # nothing of the mixer, and stands before it in program order
        with jax.named_scope(SCOPE_MOE_ROUTE_EARLY), \
                jax.named_scope(SCOPE_MOE_ROUTE):
            routing = _plan_routing(_router_rows(h), layer_params, cfg)
    h, mlp_in, side = _block_mixer(h, layer_params, cfg, mesh, attn_bias, k1,
                                   kind)
    if mlp_in is None:      # a single sublayer: the mixer alone
        out, aux = None, jnp.zeros((2,), jnp.float32)
    elif experts_of(cfg, kind) > 0:
        out, aux = _moe_mlp(mlp_in, layer_params, cfg, mesh, routing)
    else:
        out = _dense_mlp(mlp_in, layer_params, cfg, mesh)
        aux = jnp.zeros((2,), jnp.float32)
    if _aux_size(cfg) > 2:
        # a stack with dsa layers: every layer's aux has the third entry
        aux = jnp.append(aux, 0.0 if side is None else side)
    if out is None:
        return h, aux
    if cfg.sandwich_norm:
        # as for the mixer's output: `w2` (a MoE block: the combine)
        out = _norm(checkpoint_name(out, REMAT_NORM2_IN),
                    layer_params["ln2_post_scale"],
                    layer_params["ln2_post_bias"], cfg)
    h = checkpoint_name(
        h + _dropout(_residual(out, cfg), cfg.dropout_rate, k2), REMAT_X2)
    if cfg.post_ln:
        h = _norm(h, layer_params["ln2_scale"],
                  layer_params["ln2_bias"], cfg)
    return h, aux


def embed_tokens(params, tokens, cfg: TransformerConfig):
    """(..., T) int32 -> (..., T, D) embeddings (+ learned positions,
    unless the dialect carries positions via rope)."""
    T = tokens.shape[-1]
    with jax.named_scope(SCOPE_EMBED):
        h = params["embed"][tokens]
        if cfg.multipliers.embedding != 1.0:
            h = h * cfg.multipliers.embedding   # in the weights' float32
        h = h.astype(cfg.dtype)
        if cfg.use_pos_emb:
            h = h + params["pos"][:T].astype(cfg.dtype)
        return h


def _logit_scaled(h, cfg: TransformerConfig):
    """The head's input rows over ``cfg.multipliers.logits``: the logits
    divided by it, without a pass over them (Granite's 8 is exact)."""
    d = cfg.multipliers.logits
    return h if d == 1.0 else h * jnp.asarray(1.0 / d, h.dtype)


def lm_head(params, h, cfg: TransformerConfig):
    """Final norm + vocab projection -> f32 logits. In post-LN mode the
    blocks already end LayerNormed and canonical post-LN has no final LN,
    so only the projection applies, as on a looped model's exit states,
    which ``encode`` normed pass by pass. Tied configs project against the
    token embedding itself (no transposed copy is materialized)."""
    if not cfg.post_ln and cfg.n_loops == 1:
        h = _norm(h, params["lnf_scale"], params["lnf_bias"], cfg)
    with jax.named_scope(SCOPE_HEAD):
        h = _logit_scaled(h, cfg)
        if cfg.tied_head:
            return jnp.einsum("btd,vd->btv", h,
                              params["embed"].astype(h.dtype),
                              preferred_element_type=jnp.float32)
        return jnp.einsum("btd,dv->btv", h, params["head"].astype(h.dtype),
                          preferred_element_type=jnp.float32)


def nll_loss(logits, targets):
    with jax.named_scope(SCOPE_HEAD):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return jnp.mean(
            -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0])


# ---------------------------------------------------------------------------
# what the layer's `jax.checkpoint` keeps
# ---------------------------------------------------------------------------

# of the device's limit, kept free: the allocator's slack and what `encode`
# cannot see from where it stands (the loss head's working set)
_REMAT_MARGIN = 1 / 32


def _device_bytes_limit():
    """What the first local device says it may hold, None where the backend
    keeps no such count (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def _axes(mesh, *names):
    """Devices that the mesh axes ``names`` cut an array over."""
    return 1 if mesh is None else int(np.prod(
        [mesh.shape.get(n, 1) for n in names]))


def _state_bytes(cfg: TransformerConfig, params, mesh):
    """Weights, their gradient and AdamW's two moments on one device: four
    times the f32 bytes of ``params``, each leaf over what ``param_specs``
    shards it by (a leaf the trunk's specs do not know is replicated)."""
    specs = {jax.tree_util.keystr(k): spec for k, spec in
             jax.tree_util.tree_leaves_with_path(
                 param_specs(cfg), is_leaf=lambda x: isinstance(x, P))}
    return sum(
        16 * leaf.size // _axes(mesh, *(a for a in specs.get(
            jax.tree_util.keystr(k), P()) if a is not None))
        for k, leaf in jax.tree_util.tree_leaves_with_path(params))


def _block_residual_bytes(cfg: TransformerConfig, mesh, h, blocks,
                          attn_bias, kind="attention"):
    """Bytes that ONE block of ``kind``, with one run's stacked weights
    ``blocks``, has its backward pass read from its forward pass: the
    residuals of ``jax.vjp`` of ``_block`` itself, traced abstractly at the
    real shapes (nothing is compiled or run). That is the working set a
    layer's recomputation fills before its backward pass drains it, and it
    follows the block: a MoE block's rows a pick count here, an unfused
    attention's scores too. The compiler fuses some of them away, so this
    reads high (BERT-base at 65,536 tokens: 3.96 GiB)."""
    def residuals(h, layer, attn_bias):
        return jax.vjp(
            lambda h, layer: _block(h, layer, cfg, mesh, attn_bias,
                                    kind=kind),
            h, layer)[1]

    def shape(x, cut=0):
        return jax.ShapeDtypeStruct(x.shape[cut:], x.dtype)

    shapes = jax.eval_shape(
        residuals, shape(h), jax.tree.map(lambda x: shape(x, 1), blocks),
        None if attn_bias is None else shape(attn_bias))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))


def _remat_names(cfg: TransformerConfig, params, h, mesh, attn_bias=None,
                 bytes_limit=None):
    """Which named values of a block the trunk's ``jax.checkpoint`` keeps
    for the backward pass -> (names, bytes held, bytes budget), the bytes on
    one device. A pure function of what ``encode`` sees at trace time:
    shapes, the parameter tree, the mesh, and the device's own limit
    (``bytes_limit`` stands in for it in tests; the CPU reports none, and
    nothing is kept there).

    The candidates of ``REMAT_CANDIDATES`` are admitted in their order
    (latent attention's ``REMAT_MLA_LATENT`` after o and lse)
    while their bytes, times the block applications of a step that write
    them (``n_layers`` x ``n_loops``: a looped model keeps every pass's; o
    and lse: the attention layers alone, an mla layer's o at ITS width, nh *
    v_dim; the latent and the rotary key: the mla layers; q, k, v: the
    attention layers whose ``_split_heads`` makes them, k and v at
    ``kv_heads``, and the mla layers at their two widths; a window layer's
    o, lse, q, k and v at the window kind's own head count; the sandwich
    norms' inputs: under ``cfg.sandwich_norm``), stay within the budget: the
    limit less what the step holds whatever is kept (``_state_bytes``, the
    stack of layer inputs the scans keep, one an application,
    ``_block_residual_bytes`` of one block, the largest among the kinds of
    the stack, ``_REMAT_MARGIN``). A candidate that no application writes
    (the kernels read the projection in place; no sandwich norm) costs
    nothing and adds no name; of the others a later one never gets in
    without the earlier ones. The order of the last two is their rank by ms
    of the step saved a GiB kept, each measured alone on the v5e
    (``tracing.REMAT_CANDIDATES``; PERF.md, PR 36).

    A stack with "dsa" layers keeps ``REMAT_DSA_GRADS`` first and whatever
    the budget reads, a negative one too: the name is on the residuals of the
    indexer's loss, the gradient of its five leaves (their own bytes a layer,
    counted in the bytes held), and a residual is held either way: unnamed,
    the recomputation makes it again, the loss and all. Keeping it costs
    nothing of what the budget counts.

    It admits ``REMAT_DSA_MASK`` next, the selection's kept set packed by
    query (``_dsa_parts``; every dsa application x B x T x T / 32 words,
    counted in the bytes held: the ordered candidates see them taken), while
    the bytes held stay within the budget WITHOUT ``_block_residual_bytes``:
    the limit less ``_REMAT_MARGIN``, ``_state_bytes`` and the layer inputs.
    That estimate reads 8.8 GiB for keye's block, where the step's own peak on
    the v5e (14.60 of 15.75 GiB, PERF.md, PR 48) leaves the block's working
    set and the head's 7.2 together; and the bits save ~900 ms of the step a
    GiB kept, above every ordered candidate (the selection, the index scores
    and the indexer's projections run once a layer; PERF.md, PR 64). When the
    estimate is made true (ROADMAP S2.2(b)), both dsa names join the ordered
    loop."""
    if bytes_limit is None:
        bytes_limit = _device_bytes_limit()
    if bytes_limit is None:
        return (), 0, 0
    B, T, D = h.shape
    dp, sp, tp = (_axes(mesh, name) for name in ("dp", "sp", "tp"))
    # one (B, T, D) activation on one device, cut by sequence or by head
    act = B * T * D * jnp.dtype(h.dtype).itemsize // dp
    by_seq, by_head = act // sp, act // tp
    lse = B * T * cfg.n_heads * 4 // (dp * tp)
    applications = cfg.n_layers * cfg.n_loops
    # the applications that WRITE an x1 apart from their output: both halves
    # (a single sublayer's one sum is its output, which the scan keeps)
    two_halves = cfg.n_loops * sum(
        n for kind, n in layer_runs(cfg)
        if has_mlp(kind) and mixer_of(kind) != "mlp")
    # one run's stacked weights stand for its kind's shapes
    by_kind = {kind: blocks for (kind, _), blocks in zip(
        layer_runs(cfg), run_blocks(cfg, params["blocks"]))}
    # the limit less what the step holds whatever is kept: the state and the
    # layer inputs; then a block's working set
    room = (bytes_limit * (1 - _REMAT_MARGIN)
            - _state_bytes(cfg, params, mesh) - applications * by_seq)
    budget = int(
        room
        # activations carry the batch: dp cuts them, and maybe more
        - max(_block_residual_bytes(cfg, mesh, h, blocks, attn_bias, kind)
              for kind, blocks in by_kind.items()) // dp)
    attention, mla = (cfg.n_loops * sum(
        n for kind, n in layer_runs(cfg) if mixer_of(kind) in mixers)
        for mixers in (("attention", "dsa"), ("mla",)))
    # q and o at the heads' own width (`cfg.d_head`): D unless it says more
    by_head = by_head * cfg.n_heads * cfg.head_dim // D
    split = 0 if _projection_in_place(
        cfg, mesh, _resolve_attn_impl(cfg, mesh, T, attn_bias)) else attention
    # one mla layer's arrays, in columns a token over the stream's D
    m = cfg.mla
    mla_o, mla_latent, mla_qkv = ((0, 0, 0) if not mla else (
        by_head * cfg.n_heads * m.v_dim // D,
        act * (m.kv_rank + m.rope_dim) // D,
        by_head * cfg.n_heads * (2 * m.qk_dim + m.v_dim) // D))
    # {x1, x2} of every block (pre-LN: x2 is the block's output, which the
    # scan keeps anyway), {o, lse} of an attention block of either kind, the
    # latent of an mla block, {q, k, v} of an attention block on the split
    # path and of an mla block, the two sandwich norms' inputs of every
    # block: the bytes of each, times the applications that write them
    # a window layer's o, lse, q, k, v at ITS head count (always split:
    # its q and k are rotated)
    window, w_head, w_lse, w_kv = 0, 0, 0, 0
    if "window" in cfg.layer_types:
        wn = cfg.window.n_heads
        window = cfg.n_loops * sum(n for kind, n in layer_runs(cfg)
                                   if mixer_of(kind) == "window")
        w_head = act // tp * wn * cfg.head_dim // D
        w_lse = B * T * wn * 4 // (dp * tp)
        w_kv = 2 * w_head * cfg.kv_heads // wn
    costs = (two_halves * by_seq * (2 if cfg.post_ln else 1),
             attention * (by_head + lse) + mla * (mla_o + lse)
             + window * (w_head + w_lse),
             mla * mla_latent,
             split * (by_head + 2 * by_head * cfg.kv_heads // cfg.n_heads)
             + mla * mla_qkv + window * (w_head + w_kv),
             applications * 2 * by_seq if cfg.sandwich_norm else 0)
    order = (REMAT_CANDIDATES[:2] + ((REMAT_MLA_LATENT,),)
             + REMAT_CANDIDATES[2:])
    names, held, dsa = (), 0, 0
    for kind, n in layer_runs(cfg):
        if mixer_of(kind) == "dsa":
            names = (REMAT_DSA_GRADS,)
            dsa += cfg.n_loops * n
            held += cfg.n_loops * n * sum(
                leaf.size // leaf.shape[0] * leaf.dtype.itemsize
                for leaf in map(by_kind[kind].get, DSA_LEAVES))
    if dsa:
        from ..kernels.flash_attention import mask_planes
        # every dsa application's kept set by query: a bit a (query, key)
        # pair, in int32 words
        bits = dsa * B * T * (T // mask_planes(T)) * 4 // dp
        if held + bits <= room:
            names, held = names + (REMAT_DSA_MASK,), held + bits
    for candidate, cost in zip(order, costs):
        if not cost:
            continue
        if held + cost > budget:
            break
        names, held = names + candidate, held + cost
    return names, held, budget


@functools.lru_cache(maxsize=None)
def _log_remat(names, held, budget):
    """Once a distinct choice, at trace time."""
    _log.info("remat keeps %s: %.2f GiB of a budget of %.2f GiB a device",
              list(names) or "nothing", held / 2**30, budget / 2**30)


def encode(params, h, cfg: TransformerConfig, mesh: Optional[Mesh] = None,
           attn_bias=None, dropout_rng=None):
    """Run the block stack on embedded input h (B, T, D) -> (h, aux_sum
    (2,): the layers' MoE [balance, z] losses summed; ``aux_weights``).
    The trunk shared by the causal LM and the bidirectional encoder (BERT);
    ``attn_bias`` (a padding mask, constant across layers) is a scan
    constant via closure. ``dropout_rng``: training-time dropout when
    ``cfg.dropout_rate > 0`` — omit for deterministic eval.

    A looped model (``cfg.n_loops > 1``) runs the stack that many times over
    the same ``params["blocks"]``, applies the final norm after each pass and
    feeds the normed state to the next: -> (exits (n_loops, B, T, D), every
    pass's normed state, aux_sum over all passes). The passes are an outer
    ``lax.scan`` that closes over the weights: no copy of them a pass, and
    their gradient is the backward scan's running sum over passes.

    Under ``cfg.remat`` the backward pass of a layer recomputes its forward
    pass but for the named values ``_remat_names`` finds room to keep."""
    runs = layer_runs(cfg)
    block_fns = {kind: functools.partial(_block, cfg=cfg, mesh=mesh,
                                         kind=kind) for kind, _ in runs}
    if cfg.remat:
        names, held, budget = _remat_names(cfg, params, h, mesh, attn_bias)
        _log_remat(names, held, budget)
        # no name admitted: the bare checkpoint, the very program it was
        policy = (jax.checkpoint_policies.save_only_these_names(*names)
                  if names else None)
        block_fns = {kind: jax.checkpoint(fn, policy=policy)
                     for kind, fn in block_fns.items()}
    L = cfg.n_layers

    def stack(h, aux_sum, dropout_rng):
        """Every run of one kind is a scan over that run's stacked weights;
        a layer's index counts through the whole stack."""
        first = 0
        for (kind, n), blocks in zip(runs, run_blocks(cfg, params["blocks"])):
            def scan_body(carry, xs, block_fn=block_fns[kind]):
                h, aux_sum = carry
                layer_params, li = xs
                rng = (None if dropout_rng is None
                       else jax.random.fold_in(dropout_rng, li))
                h, aux = block_fn(h, layer_params, attn_bias=attn_bias,
                                  dropout_rng=rng)
                return (h, aux_sum + aux), None

            (h, aux_sum), _ = jax.lax.scan(
                scan_body, (h, aux_sum),
                (blocks, jnp.arange(first, first + n)))
            first += n
        return h, aux_sum

    no_aux = jnp.zeros((_aux_size(cfg),), jnp.float32)
    if cfg.n_loops == 1:
        return stack(h, no_aux, dropout_rng)

    def one_pass(carry, t):
        h, aux_sum = stack(*carry, None if dropout_rng is None
                           else jax.random.fold_in(dropout_rng, L + t))
        h = _norm(h, params["lnf_scale"], params["lnf_bias"], cfg)
        return (h, aux_sum), h

    (_, aux_sum), exits = jax.lax.scan(one_pass, (h, no_aux),
                                       jnp.arange(cfg.n_loops))
    return exits, aux_sum


def forward_hidden(params, tokens, cfg: TransformerConfig,
                   mesh: Optional[Mesh] = None, dropout_rng=None):
    """tokens (B, T) int32 -> (hidden (B, T, D), aux) before the LM head;
    a looped model's hidden is its (n_loops, B, T, D) exit states."""
    h = embed_tokens(params, tokens, cfg)
    h = _constrain(h, mesh, "dp", "sp", None)
    return encode(params, h, cfg, mesh, dropout_rng=dropout_rng)


def forward(params, tokens, cfg: TransformerConfig, mesh: Optional[Mesh] = None,
            dropout_rng=None):
    """tokens (B, T) int32 -> logits (B, T, V); a looped model's logits at
    every exit, (n_loops, B, T, V)."""
    h, aux_sum = forward_hidden(params, tokens, cfg, mesh,
                                dropout_rng=dropout_rng)
    if cfg.n_loops > 1:
        n, B, T, D = h.shape
        logits = lm_head(params, h.reshape(n * B, T, D), cfg)
        return logits.reshape(n, B, T, -1), aux_sum
    return lm_head(params, h, cfg), aux_sum


def _through_run(h, blocks, cfg: TransformerConfig, kind):
    """h after one run's stacked ``blocks`` of ``kind`` (no mesh)."""
    h, _ = jax.lax.scan(
        lambda h, layer: (_block(h, layer, cfg, None, kind=kind)[0], None),
        h, blocks)
    return h


def moe_routing_stats(params, tokens, cfg: TransformerConfig, terms=False):
    """What the routers of a MoE model do with ``tokens`` (B, T): a pure
    function beside the step, for counters and checks (no mesh). It walks
    the runs of ``layer_runs`` and reports the EXPERT layers, in order
    (leading axis: expert layers), E the router's width: ``picks`` (E,)
    picks an expert takes, ``experts`` (B*T, k) the experts a token picks,
    ``max_over_mean`` the fullest expert's load over the mean load,
    ``held`` the picks on experts held here (all B*T*k unless the config is
    a share, ``Router.width``), ``dropped`` the held picks that the grouped
    matmuls' group sizes do not cover (0: ``_moe_mlp`` is dropless),
    ``rows_run`` the pick rows that dispatch, activation and combine cover
    for this input (a share: ``held`` up to whole chunks, by the helper the
    step's loops take their trip count from; else all B*T*k),
    ``entropy`` the mean entropy of a token's scores as a distribution over
    the experts (the softmax itself; sigmoid scores over their sum), in
    nats. With ``terms`` also what the picks were computed from:
    ``router_in`` (B*T, D) the rows the router read (its weights are
    ``params["blocks"]["router"]``; the layer's input where
    ``Router.input`` is "block"), and ``weights`` (B*T, k) the picks' as the
    combine reads them."""
    if not cfg.n_experts:
        raise MoEConfigError("moe_routing_stats: a dense config")
    k, r = cfg.n_experts_per_tok, cfg.router
    S = tokens.shape[0] * tokens.shape[1]
    E, first, n_held = r.width or cfg.n_experts, r.first_held, cfg.n_experts

    def body(h, layer_params, kind):
        _, mlp_in = _block_attn(h, layer_params, cfg, None, None, None, kind)
        # the rows AS ROUNDED to the compute dtype, for the router and for
        # ``router_in`` alike: without the barrier the compiler may feed the
        # router's float32 matmul the norm's unrounded output (its excess
        # precision), and a check that recomputes the picks from
        # ``router_in`` then sees picks it cannot explain (PERF.md, PR 55).
        # The rows are the router's OWN input: the layer's, where it reads
        # that (``Router.input``)
        rows = _router_rows(h if r.input == "block" else mlp_in)
        top_p, top_e, counts, probs, _ = _route(rows, layer_params, cfg)
        if r.score != "softmax":
            probs = probs / jnp.sum(probs, -1, keepdims=True)
        held = jnp.sum((top_e >= first) & (top_e < first + n_held))
        covered = jnp.sum(counts[first:first + n_held])
        stats = {
            "picks": counts, "experts": top_e,
            "max_over_mean": jnp.max(counts) * E / (S * k),
            "held": held,
            "dropped": held - covered,
            # not a share: every pick is covered, so all S * k
            "rows_run": _rows_run(covered, _row_chunk(
                S, rows.shape[-1], rows.dtype), S * k),
            "entropy": -jnp.mean(jnp.sum(
                probs * jnp.log(jnp.maximum(probs, 1e-30)), -1))}
        if terms:
            stats["router_in"] = rows
            stats["weights"] = top_p
        h, _ = _block(h, layer_params, cfg, None, kind=kind)
        return h, stats

    h, stats = embed_tokens(params, tokens, cfg), []
    for (kind, _), blocks in zip(layer_runs(cfg),
                                 run_blocks(cfg, params["blocks"])):
        if experts_of(cfg, kind):
            h, of_run = jax.lax.scan(
                functools.partial(body, kind=kind), h, blocks)
            stats.append(of_run)
        else:
            h = _through_run(h, blocks, cfg, kind)
    return stats[0] if len(stats) == 1 else jax.tree.map(
        lambda *runs: jnp.concatenate(runs), *stats)


def _first_mamba_layer(params, tokens, cfg: TransformerConfig, who):
    """(the stack's FIRST layer's params, its mixer's normed input) where
    that layer is a mamba layer; ``who`` asks."""
    if mixer_of(layer_runs(cfg)[0][0]) != "mamba":
        raise ValueError(f"{who}: the stack's first layer is not a mamba "
                         f"layer (layer_types={cfg.layer_types})")
    p = jax.tree.map(lambda x: x[0], run_blocks(cfg, params["blocks"])[0])
    h = embed_tokens(params, tokens, cfg)
    return p, _norm(h, p["ln1_scale"], p["ln1_bias"], cfg)


def ssm_scan_terms(params, tokens, cfg: TransformerConfig):
    """The float32 parts of the FIRST mamba layer's scan on ``tokens`` (B,
    T), with what each was computed from: a pure function beside the step,
    for checks (no mesh). ``dt_raw`` (B, T, H) and ``dt`` = softplus(raw +
    ``dt_bias``); ``log_decay`` (B, c, Q, G, R) the cumulative dt * A over a
    chunk; ``B`` (B, c, Q, G, N) and ``xd`` (B, c, Q, G, R, P) the state
    matmul's operands; ``local`` and ``entering`` (B, c, G, R, P, N) the
    chunks' own states and the recurrence's; ``dt_bias``, ``A_log``."""
    p, h = _first_mamba_layer(params, tokens, cfg, "ssm_scan_terms")
    _, x, Bm, Cm, dt_raw = _mamba_inputs(h, p, cfg)
    dt = _ssm_dt(dt_raw, p["dt_bias"])
    x, dt_c, acs, Bm, _ = _ssd_chunks(x, dt, p["A_log"], Bm, Cm,
                                      cfg.ssm.chunk)
    xd, local, entering = _ssd_states(x, dt_c, acs, Bm)
    return {"dt_raw": dt_raw, "dt": dt, "log_decay": acs, "B": Bm, "xd": xd,
            "local": local, "entering": entering, "dt_bias": p["dt_bias"],
            "A_log": p["A_log"]}


def ssm_gate_terms(params, tokens, cfg: TransformerConfig):
    """The float32 gated norm of the FIRST mamba layer (the stack's first
    layer, as ``ssm_scan_terms``) on ``tokens`` (B, T), with what it was
    computed from: ``gated`` (B, T, d_inner) = (y + D x) SiLU(z) as the norm
    reads it, ``normed`` what the norm made of it before any cast,
    ``scale`` (d_inner,) its weight."""
    p, h = _first_mamba_layer(params, tokens, cfg, "ssm_gate_terms")
    gated = _mamba_gated(h, p, cfg, None)
    return {"gated": gated, "normed": _mamba_gate_norm(gated, p, cfg),
            "scale": p["ssm_norm"]}


def _first_layer_of(params, tokens, cfg: TransformerConfig, wanted):
    """(the residual stream entering the stack's first layer whose kind
    ``wanted(kind)`` accepts, that layer's params, its kind)."""
    h = embed_tokens(params, tokens, cfg)
    for (kind, _), blocks in zip(layer_runs(cfg),
                                 run_blocks(cfg, params["blocks"])):
        if wanted(kind):
            return h, jax.tree.map(lambda x: x[0], blocks), kind
        h = _through_run(h, blocks, cfg, kind)
    raise ValueError(f"no such layer among {layer_runs(cfg)}")


def router_terms(params, tokens, cfg: TransformerConfig):
    """The float32 parts of the FIRST expert layer's router on ``tokens``
    (B, T), with what each was computed from: a pure function beside the
    step, for checks (no mesh). ``x`` (S, D) the rows the router reads (the
    compute dtype), ``router`` (D, E), ``bias`` (E,) (zeros without one),
    ``scores`` (S, E), ``experts`` (S, k) the picks, ``weights`` (S, k)
    theirs as the combine reads them."""
    h, p, kind = _first_layer_of(params, tokens, cfg,
                                 lambda kind: experts_of(cfg, kind) > 0)
    if cfg.router.input == "block":
        mlp_in = h          # the router's rows are the layer's own input
    else:
        _, mlp_in = _block_attn(h, p, cfg, None, None, None, kind)
    x = mlp_in.reshape(-1, mlp_in.shape[-1])
    top_p, top_e, _, probs, _ = _route(x, p, cfg)
    return {"x": x, "router": p["router"], "scores": probs,
            "bias": p.get(ROUTER_BIAS, jnp.zeros(probs.shape[-1:])),
            "experts": top_e, "weights": top_p}


def short_conv_terms(params, tokens, cfg: TransformerConfig):
    """The float32 part of the FIRST conv layer's mixer on ``tokens`` (B, T)
    with what it was computed from: ``B``, ``C``, ``x`` (B, T, D) the
    in-projection's three chunks (the compute dtype), ``conv_w`` (K, D) and
    ``y`` (B, T, D) float32 = C . conv(B . x) as the out-projection's cast
    reads it."""
    h, p, _ = _first_layer_of(params, tokens, cfg,
                              lambda kind: mixer_of(kind) == "conv")
    Bg, Cg, x = _short_conv_chunks(
        _norm(h, p["ln1_scale"], p["ln1_bias"], cfg), p)
    return {"B": Bg, "C": Cg, "x": x, "conv_w": p["conv_w"],
            "y": _short_conv_gates(Bg, Cg, x, p["conv_w"])}


def mla_terms(params, tokens, cfg: TransformerConfig):
    """The FIRST mla layer's projections on ``tokens`` (B, T) with what the
    float32 part was computed from: ``x`` (B, T, D) the rows the projections
    read (the compute dtype), ``c`` (B, T, kv_rank) the latent as ``wkv_a``
    writes it, ``kv_norm`` its scale, ``latent`` float32 = RMSNorm(c) as
    ``wkv_b``'s cast reads it, and ``q``, ``k``, ``v`` as the attention
    kernels take them (their float32 part, the softmax statistic, is the
    kernels' own output: ``kernels.flash_attention._fwd_pallas``)."""
    h, p, _ = _first_layer_of(params, tokens, cfg,
                              lambda kind: mixer_of(kind) == "mla")
    x = _norm(h, p["ln1_scale"], p["ln1_bias"], cfg)
    q, k, v, (c, latent) = _mla_qkv(x, p, cfg)
    return {"x": x, "c": c, "kv_norm": p["kv_norm"], "latent": latent,
            "q": q, "k": k, "v": v}


def kda_terms(params, tokens, cfg: TransformerConfig, heads=None):
    """The float32 parts of the FIRST kda layer on ``tokens`` (B, T), with
    what each was computed from: a pure function beside the step, for checks
    and counters (no mesh). Of the heads ``heads`` (indices; None: all): ``q``,
    ``k``, ``v`` (B, T, h, K) as the scan takes them, ``g`` (B, T, h, K) the
    log-decay and ``beta`` (B, T, h), float32; ``G`` the log-decay cumulated
    over each chunk, ``U`` (B, T, h, K) the triangular system's solution,
    ``entering`` (B, chunks, h, K, K) the state entering each chunk and ``o``
    (B, T, h, K) the scan's output; ``normed`` (B, T, h * K) what the gated
    head norm made of ``o`` before any cast, with ``gate`` the sigmoid it
    multiplied by and ``scale`` the norm's; ``chunk_log_decay_min`` the most
    negative cumulated log-decay inside a chunk, over ALL heads."""
    from . import kda
    h, p, _ = _first_layer_of(params, tokens, cfg,
                              lambda kind: mixer_of(kind) == "kda")
    q, k, v, g, beta, gate_low = _kda_inputs(
        _norm(h, p["ln1_scale"], p["ln1_bias"], cfg), p, cfg)
    low = kda.chunk_log_decay_min(g, cfg.kda.chunk)
    if heads is not None:
        take = lambda x: x[:, :, jnp.asarray(heads)]
        q, k, v, g, beta = (take(x) for x in (q, k, v, g, beta))
    o, terms = kda.scan(q, k, v, g, beta, cfg.kda.chunk, terms=True)
    K = cfg.kda.head_dim
    gb = p["kda_gb"] if heads is None else jnp.concatenate(
        [p["kda_gb"][:, i * K:(i + 1) * K] for i in heads], -1)
    gate = _kda_gate(gate_low, gb, cfg)
    return {"q": q, "k": k, "v": v, "g": g, "beta": beta, "o": o, **terms,
            "normed": _kda_gate_norm(o, gate, p["kda_norm"], cfg.ln_eps),
            "scale": p["kda_norm"], "gate": gate,
            "chunk_log_decay_min": low}


def gdn_terms(params, tokens, cfg: TransformerConfig, heads=None):
    """``kda_terms`` of the FIRST gdn layer on ``tokens`` (B, T), of the
    VALUE heads ``heads`` (indices; None: all): ``q``, ``k`` (B, T, h, K) as
    the scan takes them (the key heads repeated), ``v`` (B, T, h, V), ``g``
    and ``beta`` (B, T, h) float32, the log-decay a head; ``G`` (B, T, h) the
    log-decay cumulated over each chunk, ``U``, ``entering`` and ``o`` as
    ``kda_terms`` has them; ``normed`` (B, T, h * V) what the head norm and
    SiLU(z) made of ``o`` before any cast, with ``gate`` = SiLU(z) and
    ``scale`` the norm's; ``chunk_log_decay_min`` over ALL heads. Through the
    form of the rule the step runs (``kda.scan``'s own rule)."""
    from . import kda
    h, p, _ = _first_layer_of(params, tokens, cfg,
                              lambda kind: mixer_of(kind) == "gdn")
    q, k, v, g, beta, z = _gdn_inputs(
        _norm(h, p["ln1_scale"], p["ln1_bias"], cfg), p, cfg)
    low = kda.chunk_log_decay_min(g, cfg.gdn.chunk)
    gate = jax.nn.silu(z.astype(jnp.float32))
    # a VALUE head's q and k, as the dict returns them
    q, k = (jnp.repeat(x, cfg.gdn.n_v_heads // cfg.gdn.n_k_heads, axis=2)
            for x in (q, k))
    if heads is not None:
        take = lambda x: x[:, :, jnp.asarray(heads)]
        q, k, v, g, beta = (take(x) for x in (q, k, v, g, beta))
        V = cfg.gdn.v_dim
        gate = jnp.concatenate(
            [gate[..., i * V:(i + 1) * V] for i in heads], -1)
    # the arrays returned are the arrays the scan READS: without the barrier
    # the TPU compiler hands the XLA form q, k and v from BEFORE their
    # rounding to the compute dtype (excess precision: a cast down and up
    # again is dropped), 1.7e-3 from what is returned here
    q, k, v, g, beta = jax.lax.optimization_barrier((q, k, v, g, beta))
    o, terms = _gdn_scan(q, k, v, g, beta, cfg, terms=True)
    return {"q": q, "k": k, "v": v, "g": g, "beta": beta, "o": o, **terms,
            "normed": _kda_gate_norm(o, gate, p["gdn_norm"], cfg.ln_eps),
            "scale": p["gdn_norm"], "gate": gate,
            "chunk_log_decay_min": low}


def attention_terms(params, tokens, cfg: TransformerConfig, mixer):
    """The FIRST layer of mixer ``mixer`` ("attention" or "window") on
    ``tokens`` (B, T), with what its float32 parts were computed from: a pure
    function beside the step, for checks (no mesh). ``x`` (B, T, D) the rows
    the projections read (the compute dtype), ``q_raw``, ``k_raw`` (B, T,
    heads * hd) and (B, T, kv_heads * hd) the projection's q and k columns
    before anything touches them, ``q``, ``k``, ``v`` (B, T, heads * hd) as
    the attention kernels take them (``_split_heads``: rotated, the k/v
    heads broadcast), ``wg`` (D, heads) and ``wo`` (heads * hd, D) the gate's
    and the output projection's weights, and ``out`` (B, T, D) what the layer's OWN mixer (``_KINDS``: the function
    ``_block`` calls, with the window it hands its kernels) makes of ``x``
    and adds to the stream."""
    h, p, _ = _first_layer_of(params, tokens, cfg,
                              lambda kind: mixer_of(kind) == mixer)
    view = _mixer_view(cfg, mixer)
    x = _norm(h, p["ln1_scale"], p["ln1_bias"], cfg)
    qkv = jnp.einsum("btd,de->bte", x, p["wqkv"].astype(x.dtype),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    nh, hd = view.n_heads, view.head_dim
    q_raw, k_raw, _ = jnp.split(qkv, [nh * hd, (nh + view.kv_heads) * hd],
                                axis=-1)
    q, k, v = _split_heads(qkv, p, view, None, "flash")
    return {"x": x, "q_raw": q_raw, "k_raw": k_raw, "q": q, "k": k, "v": v,
            "wg": p.get("wg"), "wo": p["wo"],
            "out": _KINDS[mixer].mixer(x, p, cfg, None, None)}


def attention_visits(params, tokens, cfg: TransformerConfig, mixer, chunk):
    """The (query, key) pairs the FIRST layer of mixer ``mixer`` COMPUTES on
    ``tokens`` (1, T), MEASURED through the layer's own mixer (``_KINDS``)
    -> int32 (T // chunk,): the query rows that READ each chunk of ``chunk``
    keys; their sum x ``chunk`` is the pairs computed (exact where ``chunk``
    divides the kernels' key tile). One chunk of the layer's input rows at a
    time is made NaN: a score the mask drops becomes a probability of 0, and
    0 x NaN in the product with v is NaN, so every row of a query tile that
    VISITS the tile holding the chunk comes out NaN whatever the mask says,
    and a row whose pass never reads the chunk does not (its own rows read
    it on the diagonal). T / chunk passes of one layer: for checks."""
    h, p, _ = _first_layer_of(params, tokens, cfg,
                              lambda kind: mixer_of(kind) == mixer)
    x = _norm(h, p["ln1_scale"], p["ln1_bias"], cfg)
    T = x.shape[1]

    def rows_that_read(c):
        bad = (jnp.arange(T) // chunk == c)[None, :, None]
        out = _KINDS[mixer].mixer(jnp.where(bad, jnp.nan, x), p, cfg, None,
                                  None)
        return jnp.sum(jnp.any(jnp.isnan(out), -1), dtype=jnp.int32)

    return jax.lax.map(rows_that_read, jnp.arange(T // chunk))


def dsa_stats(params, tokens, cfg: TransformerConfig, terms=False):
    """What the indexers of a model with learned sparse attention do with
    ``tokens`` (B, T): the forward pass of the step itself (``_dsa_parts``,
    the function ``_block`` runs), layer by layer, for counters and checks
    (no mesh). Leading axis: the "dsa" layers, in order. ``kept`` (B,) the
    (query, key) pairs a sequence keeps, ``causal`` the pairs it has (T (T +
    1) / 2: the kept share is 100 % while ``top_k`` >= T), ``loss`` the
    layer's L_I. With ``terms`` also what the selection was computed from
    and what it gave: ``qI`` (B, T, J * c), ``kI`` (B, T, c) and ``w`` (B, T,
    J) the indexer's rotated queries, key and weights, ``by_query`` (B, T,
    W) the kept set's packed mask (``flash_attention.pack_row_mask``)."""
    from ..kernels import dsa
    if "dsa" not in cfg.layer_types:
        raise ValueError("dsa_stats: no dsa layer among "
                         f"{layer_kinds(cfg)}")
    T = tokens.shape[1]

    def body(h, layer_params, kind):
        x = _norm(h, layer_params["ln1_scale"], layer_params["ln1_bias"],
                  cfg)
        _, loss, kept = _dsa_parts(x, layer_params, cfg, None)
        stats = {"kept": kept, "causal": jnp.asarray(T * (T + 1) // 2),
                 "loss": loss}
        if terms:
            qI, kI, w = _dsa_index(x, layer_params, cfg)
            stats.update(qI=qI, kI=kI, w=w, by_query=dsa.select(
                qI, kI, w, cfg.dsa.top_k)[0][0])
        h, _ = _block(h, layer_params, cfg, None, kind=kind)
        return h, stats

    h, stats = embed_tokens(params, tokens, cfg), []
    for (kind, _), blocks in zip(layer_runs(cfg),
                                 run_blocks(cfg, params["blocks"])):
        if mixer_of(kind) == "dsa":
            h, of_run = jax.lax.scan(
                functools.partial(body, kind=kind), h, blocks)
            stats.append(of_run)
        else:
            h = _through_run(h, blocks, cfg, kind)
    return stats[0] if len(stats) == 1 else jax.tree.map(
        lambda *runs: jnp.concatenate(runs), *stats)


def attention_pairs(cfg: TransformerConfig, T):
    """The (query, key) pairs a sequence of ``T`` tokens costs one layer of
    each attention kind of the stack, by the bounds the step's own kernels
    loop to -> {"attention" | "window": {``layers``, ``heads``, ``causal``
    T (T + 1) / 2, ``kept`` the pairs the mathematics keeps (the causal ones;
    under a window W, sum over t of min(t + 1, W)), ``computed`` the pairs
    the forward pass computes, ``tiles`` its (block_q, block_k)}}, Python
    ints. On the flash path ``computed`` is whole tiles visited x tile size,
    the tiles those ``flash_attention.window_bounds`` gives for the call's
    chosen tiles (the loops' bounds are functions of position alone, so the
    count at trace time IS the forward pass's); the dot path computes every
    pair under its mask, T x T. A pair is counted once a head group, as one
    head's: every head of a layer does the same."""
    from ..kernels import flash_attention as fa
    impl = _resolve_attn_impl(cfg, None, T)
    out = {}
    for mixer in ("attention", "window"):
        layers = sum(n for kind, n in layer_runs(cfg)
                     if mixer_of(kind) == mixer)
        if not layers:
            continue
        view = _mixer_view(cfg, mixer)
        W = cfg.window.window if mixer == "window" else None
        w = min(W or T, T)
        stats = {"layers": layers, "heads": view.n_heads,
                 "causal": T * (T + 1) // 2,
                 "kept": w * (w + 1) // 2 + (T - w) * w}
        if impl == "flash":
            bq, bk, _ = fa._choose_tiles(T, view.head_dim, cfg.dtype, True,
                                         view.n_heads, window=W)
            fwd, _ = fa.window_bounds(T, W, bq, bk)
            stats.update(computed=sum(hi - lo for lo, hi in fwd) * bq * bk,
                         tiles=(bq, bk))
        else:
            stats.update(computed=T * T, tiles=(T, T))
        out[mixer] = stats
    return out


def aux_weights(aux_weight=0.01, size=2, router=None):
    """Weights of ``encode``'s aux (``size``,): the balance loss takes the
    caller's ``aux_weight``, the router z-loss ``Z_LOSS_WEIGHT`` (both
    ``router.loss_weights`` where a model's router states them), the
    indexers' loss (``_aux_size``: a third entry) ``DSA_LOSS_WEIGHT``."""
    balance, z = (router and router.loss_weights) or (aux_weight,
                                                       Z_LOSS_WEIGHT)
    return jnp.array([balance, z, DSA_LOSS_WEIGHT][:size], jnp.float32)


def _fused_head_nll(params, h, targets, cfg: TransformerConfig):
    """NLL of every row of hidden ``h`` (..., D) against ``targets`` (...)
    through the vocabulary head by the fused linear+CE kernel -> (N,): the
    (N, V) logits never exist in HBM, and both weight orientations are
    kernel-native (no vocab-sized transpose): tied configs stream the (V, D)
    embedding, untied the (D, V) head."""
    from ..kernels.fused_ce import fused_linear_nll
    with jax.named_scope(SCOPE_HEAD):
        if cfg.tied_head:
            w, layout = params["embed"].astype(h.dtype), "vd"
        else:
            w, layout = params["head"].astype(h.dtype), "dv"
        V = w.shape[0] if layout == "vd" else w.shape[1]
        h = _logit_scaled(h, cfg)
        return fused_linear_nll(h.reshape(-1, h.shape[-1]), w,
                                jnp.zeros((V,), jnp.float32),
                                targets.reshape(-1), w_layout=layout)


def _exit_log_q(params, exits):
    """Exit states (n_loops, ..., D) -> log q (n_loops, ...), float32: the
    gate's stop probability at exit t is sigmoid(w . h_t + b), q(t) = stop_t
    x prod_{j<t} (1 - stop_j), and the last exit takes what is left, so that
    q sums to 1 over exits (its own gate output is not read)."""
    z = jnp.einsum("n...d,d->n...", exits.astype(jnp.float32),
                   params["exit_gate_w"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) + params["exit_gate_b"]
    go = jax.nn.log_sigmoid(-z)                    # log (1 - stop_t)
    passed = jnp.cumsum(go, 0) - go                # log prod_{j<t} (1 - stop_j)
    return jnp.concatenate(
        [(passed + jax.nn.log_sigmoid(z))[:-1], passed[-1:]], 0)


def _exit_loss(params, exits, aux, targets, cfg: TransformerConfig, mesh,
               aux_weight):
    """``exit_loss_terms`` from the exit states on."""
    from ..kernels.fused_ce import should_fuse
    n, B, T, D = exits.shape
    with jax.named_scope(SCOPE_EXIT):
        log_q = _exit_log_q(params, exits)
        q = jnp.exp(log_q)
        if should_fuse(cfg.fused_lm_ce, mesh):
            nll = _fused_head_nll(params, exits, jnp.tile(targets, (n, 1)),
                                  cfg)
        else:
            logp = jax.nn.log_softmax(
                lm_head(params, exits.reshape(n * B, T, D), cfg), -1)
            nll = -jnp.take_along_axis(
                logp, jnp.tile(targets, (n, 1))[..., None], -1)
        nll = nll.reshape(n, B, T)
        per = jnp.sum(q * nll + EXIT_ENTROPY_WEIGHT * q * log_q, 0)
        loss = jnp.mean(per) + aux_weights(aux_weight, aux.size, cfg.router) @ aux
    return loss, {"nll": nll, "q": q, "log_q": log_q, "exits": exits}


def exit_loss_terms(params, tokens, targets, cfg: TransformerConfig,
                    mesh=None, aux_weight=0.01, dropout_rng=None):
    """A looped model's training loss -> (loss, {"nll" (n_loops, B, T)
    next-token NLL at every exit, "q" (n_loops, B, T) the exit distribution
    and "log_q" its logarithm, "exits" (n_loops, B, T, D)}). The expected
    loss over exit steps less an entropy bonus (Ouro stage I): mean over
    tokens of sum_t q(t) NLL_t - ``EXIT_ENTROPY_WEIGHT`` x H(q), plus the MoE
    terms of ``loss_fn``. The n_loops head passes are ONE call of the fused
    kernel on the stacked rows (four calls of a pass's rows take the v5e the
    same 548.7 ms a step; PERF.md, PR 29); gate, q, entropy and the
    weighting are float32."""
    exits, aux = forward_hidden(params, tokens, cfg, mesh,
                                dropout_rng=dropout_rng)
    return _exit_loss(params, exits, aux, targets, cfg, mesh, aux_weight)


def exit_stats(params, tokens, cfg: TransformerConfig):
    """Where a looped model's gate would leave ``tokens`` (B, T): a pure
    function beside the step, for counters and checks (no mesh).
    ``q_mean`` (n_loops,) the mean exit probability of each exit over the
    tokens, ``expected_exit_step`` sum_t t x q_mean(t), exits counted from
    1."""
    if cfg.n_loops == 1:
        raise ValueError("exit_stats: n_loops = 1, a model with one exit")
    exits, _ = forward_hidden(params, tokens, cfg)
    q_mean = jnp.mean(jnp.exp(_exit_log_q(params, exits)), (1, 2))
    return {"q_mean": q_mean, "expected_exit_step":
            jnp.sum(q_mean * jnp.arange(1, cfg.n_loops + 1))}


def loss_fn(params, tokens, targets, cfg: TransformerConfig, mesh=None,
            aux_weight=0.01, dropout_rng=None):
    """Next-token cross-entropy + ``aux_weight`` x the MoE balance loss +
    ``Z_LOSS_WEIGHT`` x the router z-loss, both summed over layers (zero
    for dense blocks), + ``DSA_LOSS_WEIGHT`` x the indexers' loss of the
    "dsa" layers, summed over layers (``_dsa_parts``: ONE scalar, whose
    gradient gives the trunk's leaves the first three terms' and the
    indexers' leaves the last's alone). A looped model (``cfg.n_loops > 1``) takes the
    expected loss over its exits instead (``exit_loss_terms``)."""
    from ..kernels.fused_ce import should_fuse
    if cfg.n_loops > 1:
        return exit_loss_terms(params, tokens, targets, cfg, mesh, aux_weight,
                               dropout_rng)[0]
    if should_fuse(cfg.fused_lm_ce, mesh):
        h, aux = forward_hidden(params, tokens, cfg, mesh,
                                dropout_rng=dropout_rng)
        if not cfg.post_ln:
            h = _norm(h, params["lnf_scale"], params["lnf_bias"], cfg)
        per = _fused_head_nll(params, h, targets, cfg)
        return jnp.mean(per) + aux_weights(aux_weight, aux.size, cfg.router) @ aux
    logits, aux = forward(params, tokens, cfg, mesh, dropout_rng=dropout_rng)
    return nll_loss(logits, targets) + aux_weights(aux_weight, aux.size, cfg.router) @ aux


# ---------------------------------------------------------------------------
# train step (adamw fused into the step, buffers donated)
# ---------------------------------------------------------------------------

def count_params(params) -> int:
    """Total parameter count of any params pytree (shared by every model
    family — bert/vit re-export it)."""
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def init_opt_state(params):
    zeros = lambda p: jnp.zeros_like(p)
    return {"m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
            "t": jnp.zeros((), jnp.float32)}


def _is_router_bias(path):
    """Whether a leaf's tree path ends in ``ROUTER_BIAS``."""
    return getattr(path[-1], "key", None) == ROUTER_BIAS


def adamw_update(params, grads, opt_state, lr=1e-3, b1=0.9, b2=0.999,
                 eps=1e-8, wd=0.01):
    """AdamW on every leaf but a router's selection bias (``ROUTER_BIAS``):
    that leaf, its two slots and its "gradient" (the picks' counts,
    ``_noting_picks``) pass through untouched; ``move_router_bias`` is its
    rule."""
    t = opt_state["t"] + 1.0
    on_path = jax.tree_util.tree_map_with_path
    new_m = on_path(lambda path, m, g: m if _is_router_bias(path)
                    else b1 * m + (1 - b1) * g, opt_state["m"], grads)
    new_v = on_path(lambda path, v, g: v if _is_router_bias(path)
                    else b2 * v + (1 - b2) * g * g, opt_state["v"], grads)

    def upd(path, p, m, v):
        if _is_router_bias(path):
            return p
        mh = m / (1 - b1 ** t)
        vh = v / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
    new_params = on_path(upd, params, new_m, new_v)
    return new_params, {"m": new_m, "v": new_v, "t": t}


def move_router_bias(params, counts, opt_state, rate):
    """The auxiliary-loss-free balancing rule (Wang et al. 2024,
    arXiv:2408.15664) on every ``ROUTER_BIAS`` leaf (layers, E): b_e += rate
    x sign(mean(c) - c_e), ``counts`` a tree like ``params`` whose leaf of
    that name holds c, the picks each expert took in the step's batch (what
    ``jax.grad`` of ``loss_fn`` returns there). No gradient, no weight
    decay, no moment; the leaf's first AdamW slot is WRITTEN with c instead,
    so that a job reads the picks of its last step there (a counter that
    costs the step E floats a layer and no second pass), the second is left
    alone. Other leaves pass through. -> (params, opt_state)."""
    on_path = jax.tree_util.tree_map_with_path
    new_params = on_path(
        lambda path, b, c: b + rate * jnp.sign(
            jnp.mean(c, -1, keepdims=True) - c) if _is_router_bias(path)
        else b, params, counts)
    new_m = on_path(lambda path, m, c: c if _is_router_bias(path) else m,
                    opt_state["m"], counts)
    return new_params, {**opt_state, "m": new_m}


def zero1_opt_specs(cfg: TransformerConfig, mesh: Mesh):
    """ZeRO-1 (optimizer-state sharding over dp): each AdamW m/v slot is
    additionally sharded over the ``dp`` axis on its first free, divisible
    dimension. GSPMD then materializes the classic dataflow on its own —
    gradients reduce-scatter into the shard, the update computes sharded,
    and the fresh params all-gather back to their training layout
    (the 'Automatic Cross-Replica Sharding of Weight Update' recipe,
    arXiv:2004.13336, expressed as sharding annotations). Memory:
    optimizer state shrinks by ~dp x; step math is bit-identical."""
    dp = mesh.shape["dp"]
    specs = param_specs(cfg)
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(
        lambda s, sh: shard_first_free_dim(s, sh, dp), specs, shapes,
        is_leaf=lambda x: isinstance(x, P))


def shard_first_free_dim(spec, shape, dp: int):
    """Add 'dp' to a PartitionSpec on the first unsharded, dp-divisible
    dimension (the ZeRO-1 slot layout rule — shared with the pipeline
    builders, whose block specs carry a leading 'pp' dim)."""
    parts = tuple(spec) + (None,) * (len(shape.shape) - len(tuple(spec)))
    for ax, part in enumerate(parts):
        if part is None and shape.shape[ax] % dp == 0:
            return P(*parts[:ax], "dp", *parts[ax + 1:])
    return P(*parts)


def place_opt_state(opt_state, specs, mesh: Mesh):
    """Place an AdamW state on the mesh: m/v per the param specs, the
    step counter replicated — the one placement recipe shared by the
    trunk and both pipeline schedule builders."""
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, P))
    put = functools.partial(jax.tree.map, jax.device_put)
    return {"m": put(opt_state["m"], shard), "v": put(opt_state["v"], shard),
            "t": jax.device_put(opt_state["t"], NamedSharding(mesh, P()))}


def shard_opt_state(opt_state, cfg: TransformerConfig, mesh: Mesh,
                    zero1: bool = False):
    """Place an optimizer state on the mesh — the ZeRO-1 layout when
    ``zero1`` (jit pins committed input shardings, so the state must be
    placed before the first step)."""
    specs = zero1_opt_specs(cfg, mesh) if zero1 else param_specs(cfg)
    return place_opt_state(opt_state, specs, mesh)


def _loose(x) -> bool:
    """A leaf jit reads as uncommitted: a jax array no ``device_put`` or
    committed program made, or host data. Not a tracer: under an outer
    trace the step is part of that program."""
    return (not isinstance(x, jax.core.Tracer)
            and not getattr(x, "committed", False))


def _commit_state(args):
    """``args`` with the state, its first two, committed to the ONE device
    the committed leaves of ``args`` are on. jit makes uncommitted and
    committed arguments two programs (the lowered text differs by the
    committed ones' ``sdy.sharding``: two lowerings, two compilations, two
    cache keys), and a step's outputs are committed as soon as one argument
    is: ``jax.jit(init)(key)``'s uncommitted state beside a ``device_put``
    batch compiled the step on call 1 and AGAIN on call 2. ``device_put`` of
    an array to the device it is on makes a new handle on the same buffer:
    nothing is copied, and donating the handle frees the caller's too.
    Nothing committed: nothing to do (jit keeps the outputs uncommitted, so
    call 2 reads as call 1). Committed leaves on several devices are GSPMD's
    by the arguments' own shardings, or jit's own error."""
    state = jax.tree.leaves(args[:2])
    if not any(map(_loose, state)):
        return args
    devices = {d for x in jax.tree.leaves(args[2:]) + state
               if getattr(x, "committed", False) for d in x.devices()}
    if len(devices) != 1:
        return args
    return (*jax.device_put(args[:2], devices.pop()), *args[2:])


class StateStep:
    """The one-device train step: ``jax.jit(step, donate_argnums=(0, 1))``
    called with its state committed (``_commit_state``), so that the first
    call and every later one, on the step's own outputs, are one program.
    ``lower``, ``trace``, ``eval_shape`` and ``clear_cache`` are the
    jit's own, on the arguments as given."""

    def __init__(self, step):
        self._jitted = jax.jit(step, donate_argnums=(0, 1))

    def __call__(self, *args, **kwargs):
        return self._jitted(*_commit_state(args), **kwargs)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def make_train_step(cfg: TransformerConfig, mesh: Optional[Mesh] = None,
                    lr=1e-3, accum_steps: int = 1, zero1: bool = False):
    """Returns jitted (params, opt_state, tokens, targets) ->
    (loss, params, opt_state) with GSPMD dp/tp/sp/ep sharding.

    Without a mesh the state may be handed over committed or not
    (``jax.jit(init)(key)``'s outputs, a ``device_put`` tree, a checkpoint's
    numpy arrays): the step commits it to the device of its committed
    arguments without a copy, and compiles ONCE.

    ``zero1=True`` (mesh only): AdamW m/v shard over dp — see
    ``zero1_opt_specs``; place the state with
    ``shard_opt_state(opt, cfg, mesh, zero1=True)`` before the first
    step. Optimizer state memory drops ~dp x; numerics are unchanged
    (the same update, computed shard-wise).

    ``accum_steps > 1``: gradient accumulation — tokens/targets gain a
    leading accumulation axis (A, B, T); microbatch grads are averaged by a
    ``lax.scan`` (one compiled block, sequential activation memory) before
    the single optimizer apply, numerically identical to one big batch of
    A*B under mean-loss (with dropout OFF; each microbatch draws its own
    dropout mask, so the dropout-on accumulation is the usual
    independent-masks estimate, not a big-batch replica).

    ``cfg.dropout_rate > 0``: the step takes a trailing ``dropout_rng``
    argument (pass a fresh fold of your training key each step).

    ``cfg.router.bias``: the routers' selection biases are moved after the
    AdamW apply by ``move_router_bias`` at ``cfg.router.bias_rate``, from
    the picks the step's own forward pass counted."""
    use_dropout = cfg.dropout_rate > 0.0

    def step(params, opt_state, tokens, targets, dropout_rng=None):
        if use_dropout:
            # a forgotten key must not silently train WITHOUT dropout
            assert dropout_rng is not None, (
                "cfg.dropout_rate > 0: pass dropout_rng to the train step")
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(scoped(SCOPE_FWD, loss_fn))(
                params, tokens, targets, cfg, mesh,
                dropout_rng=dropout_rng)
        else:
            assert tokens.shape[0] == accum_steps, (
                f"leading (accumulation) axis {tokens.shape[0]} != "
                f"accum_steps {accum_steps}")

            def micro(carry, xs):
                loss_sum, gsum = carry
                tok, tgt, mi = xs
                rng = (None if dropout_rng is None
                       else jax.random.fold_in(dropout_rng, mi))
                l, g = jax.value_and_grad(scoped(SCOPE_FWD, loss_fn))(
                    params, tok, tgt, cfg, mesh, dropout_rng=rng)
                return (loss_sum + l,
                        jax.tree.map(jnp.add, gsum, g)), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            (loss_sum, gsum), _ = jax.lax.scan(
                micro, (jnp.zeros((), jnp.float32), zeros),
                (tokens, targets, jnp.arange(accum_steps)))
            loss = loss_sum / accum_steps
            grads = jax.tree.map(lambda g: g / accum_steps, gsum)
        new_params, new_opt = scoped(SCOPE_OPT, adamw_update)(
            params, grads, opt_state, lr=lr)
        if cfg.router.bias:
            # the picks the differentiated forward pass counted
            new_params, new_opt = scoped(SCOPE_OPT, move_router_bias)(
                new_params, grads, new_opt, cfg.router.bias_rate)
        return loss, new_params, new_opt

    if not use_dropout:
        # keep the historical 4-arg signature for deterministic configs
        det = lambda params, opt_state, tokens, targets: step(  # noqa: E731
            params, opt_state, tokens, targets)
        step_fn = det
    else:
        step_fn = step

    if mesh is None:
        return StateStep(step_fn)

    specs = param_specs(cfg)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
    if zero1:
        oshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                              zero1_opt_specs(cfg, mesh),
                              is_leaf=lambda x: isinstance(x, P))
    else:
        oshard = pshard
    opt_shard = {"m": oshard, "v": oshard,
                 "t": NamedSharding(mesh, P())}
    data_shard = NamedSharding(mesh, P(("dp",), None) if accum_steps == 1
                               else P(None, ("dp",), None))
    in_sh = (pshard, opt_shard, data_shard, data_shard)
    if use_dropout:
        in_sh = in_sh + (NamedSharding(mesh, P()),)   # replicated rng key
    return jax.jit(
        step_fn,
        in_shardings=in_sh,
        out_shardings=(NamedSharding(mesh, P()), pshard, opt_shard),
        donate_argnums=(0, 1),
    )


def shard_params(params, cfg: TransformerConfig, mesh: Mesh):
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)
