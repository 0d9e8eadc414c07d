"""Model architecture configs for the TPU executor.

The reference delegates all model execution to Ollama's catalog (models are
just names + inferred metadata, `discovery.go:482-560`). Here models are real
in-process architectures. Flagship targets per BASELINE.json configs:
Llama-3.1-8B (decoder, chat), nomic-embed-text and qwen3-embedding-8b
(encoders, embeddings with Matryoshka truncation).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch: str = "llama"  # llama (causal decoder) | encoder (bidirectional embedder)
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_hidden: int = 14_336
    head_dim: int = 0  # 0 → dim // n_heads
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 131_072
    # MoE fields (0 experts → dense FFN)
    n_experts: int = 0
    experts_per_tok: int = 2
    capacity_factor: float = 1.25
    # encoder-only fields
    pooling: str = "mean"  # mean | cls
    embed_dim: int = 0  # output embedding dim (0 → dim)
    # encoder (BERT-family) variation knobs — one shared bidirectional
    # encoder serves nomic/BERT checkpoints the way one decoder serves the
    # llama families (models/embedder.py honors all of these):
    enc_norm: str = "rms"  # rms | layer (LayerNorm with learned bias)
    enc_post_ln: bool = False  # BERT/nomic: post-LN residuals + embedding LN
    enc_pos: str = "rope"  # rope | learned (absolute position table)
    enc_gated: bool = True  # gated MLP (SwiGLU); False = fc1→act→fc2 (BERT)
    enc_bias: bool = False  # biases on attention/MLP linears (classic BERT)
    type_vocab_size: int = 0  # BERT segment embeddings (segment 0 at inference)
    # family variation knobs (one shared decoder serves all families, the
    # way the reference's one Ollama runtime serves its whole catalog):
    qkv_bias: bool = False  # Qwen2: biases on q/k/v projections
    qk_norm: bool = False  # Qwen3: per-head RMSNorm on q/k before rope
    act: str = "silu"  # FFN activation: silu (llama/qwen/mistral) | gelu (gemma)
    norm_weight_offset: float = 0.0  # Gemma: RMSNorm computes x * (1 + w)
    embed_scale: bool = False  # Gemma: hidden = embed * sqrt(dim)
    # Granite's four multipliers, each neutral by default (models/llama.py:
    # `_embed_in`, `_residual`, `_logits`; `attn_scale` below): the embedding
    # is multiplied by `embed_multiplier`, each sub-layer's output by
    # `residual_multiplier` before it joins the stream, the logits are divided
    # by `logits_divisor`, and the attention scores are multiplied by
    # `attn_multiplier` INSTEAD of head_dim**-0.5 (0 = the usual scale)
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_divisor: float = 1.0
    attn_multiplier: float = 0.0
    logit_softcap: float = 0.0  # Gemma2: logits = cap * tanh(logits / cap)
    attn_softcap: float = 0.0  # Gemma2: same cap on attention scores
    sliding_window: int = 0  # the window layers' size (0 = no layer slides)
    # the window of EACH layer as the family publishes it, 0 = a global layer
    # (Mistral: the size on every layer; Gemma2: size, 0 alternating;
    # K-EXAONE: 128, 128, 128, 0 repeated). Empty = every layer is global.
    # `periodic_windows` writes a fixed period out. In the dense decoder
    # (models/llama.py) every layer keeps a full-length cache and the window is
    # a mask; in the decoder of unlike layers (models/hybrid.py, chosen by
    # `gqa_layers`) a window layer keeps a RING of `ring_len` positions a slot
    sliding_windows: tuple[int, ...] = ()
    # Gemma2 query_pre_attn_scalar: scores scale by this**-0.5 instead of
    # head_dim**-0.5 (9B: dim/n_heads = 224 while head_dim = 256). 0 → head_dim.
    query_pre_attn_scalar: float = 0.0
    post_norms: bool = False  # Gemma2: extra RMSNorm after attn and after FFN
    # MLA (DeepSeek-V2/V3 multi-head latent attention, arch="mla"): q/kv
    # project through low-rank latents; the KV cache stores ONE latent
    # vector (+ a shared rope key) per token instead of per-head K/V —
    # kv_lora_rank + qk_rope_head_dim floats/token vs 2*n_kv_heads*head_dim
    # (e.g. 576 vs 2048 at 8B-class GQA: ~3.6x more context per HBM byte).
    q_lora_rank: int = 0  # 0 → dense q projection (V2-Lite style)
    kv_lora_rank: int = 0  # >0 enables MLA
    qk_rope_head_dim: int = 0  # per-head rope dims (shared key)
    qk_nope_head_dim: int = 0  # per-head non-rope dims
    v_head_dim: int = 0  # per-head value dims
    # rope scaling for long context: factor > 1 switches
    # `ops/rope.py:rope_tables` to the family's corrected frequencies —
    # rope_type "yarn" (DeepSeek-V2; yarn_mscale_all_dim also scales
    # attention scores via attn_scale/mla_scale) or "llama3" (Llama-3.x
    # wavelength-banded scaling)
    rope_type: str = "yarn"
    rope_factor: float = 1.0
    rope_orig_max: int = 0  # original_max_position_embeddings pre-scaling
    llama3_low_freq_factor: float = 1.0
    llama3_high_freq_factor: float = 4.0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 0.0
    yarn_mscale_all_dim: float = 0.0
    # DeepSeek-MoE structure (beyond the Mixtral-style all-MoE fields above):
    # `n_shared_experts` dense always-on experts added to the routed output;
    # routed experts use `moe_ffn_hidden` (0 → ffn_hidden); the first
    # `first_dense_layers` decoder layers keep a dense FFN (V2-Lite: 1);
    # norm_topk_prob=False keeps raw softmax gates (scaled by
    # routed_scaling_factor) instead of renormalizing the top-k
    n_shared_experts: int = 0
    moe_ffn_hidden: int = 0
    first_dense_layers: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # An expert-parallel SHARE (models/moe.py:moe_share_ffn): the router scores
    # `n_router_experts` (the published count; 0 → n_experts, no share), this
    # process holds experts [0, n_experts) and adds their part of the result
    # only. `router_score` is the one routing function's score: softmax, or
    # sigmoid with a selection bias that chooses and does not weigh.
    n_router_experts: int = 0
    router_score: str = "softmax"  # softmax | sigmoid
    # A decoder of unlike layers (models/hybrid.py): layer i is a GQA layer
    # with rows of the full-length KV cache iff i in gqa_layers, else a layer
    # whose per-slot state has a fixed size (`recurrent_kind`): a Mamba-2
    # state-space layer (models/ssm.py) where `ssm_heads` is set, a gated
    # delta-rule layer (models/kda.py: KDA or Gated DeltaNet) where `lin_heads`
    # is, a gated short convolution (models/shortconv.py) where `conv_taps` is,
    # else a WINDOW attention layer (`sliding_windows`) on a ring of its last
    # positions. Empty = every layer is the family's attention layer.
    gqa_layers: tuple[int, ...] = ()
    gqa_interval: int = 0  # linear layers between two GQA layers (published)
    lin_heads: int = 0
    lin_head_dim: int = 0  # key head size of the linear layers (and the value's, unless stated)
    lin_value_dim: int = 0  # value head size of the linear layers (0 -> lin_head_dim)
    lin_conv: int = 4  # taps of the causal depthwise convolution on q, k, v
    lin_neg_eigval: bool = False  # beta in (0, 2): negative eigenvalues allowed
    # how a linear layer makes its decay, beta and output gate (models/kda.py):
    # "kda": a decay a key CHANNEL through a low-rank pair, a sigmoid output
    # gate through another (Kimi Delta Attention); "gdn": ONE decay a head,
    # projected from the input at full rank, a SiLU output gate at full rank
    # (Gated DeltaNet)
    lin_gates: str = "kda"  # kda | gdn
    # A Mamba-2 state-space layer (models/ssm.py): `ssm_heads` heads of
    # `ssm_head_dim` values each (the inner width is their product), a state of
    # `ssm_state` keys a head, B and C ONE group shared by every head, a causal
    # depthwise convolution of `ssm_conv` taps WITH bias over x | B | C
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    # A gated short convolution layer (models/shortconv.py; LFM2's `conv`):
    # taps of its causal depthwise convolution over the product B * x at the
    # model's width, no bias, no activation. Its per-slot state is the
    # convolution's tail alone: no matrix state
    conv_taps: int = 0
    attn_gate: bool = False  # GQA output gate: attn * sigmoid(x W_gate)
    use_rope: bool = True  # False: no positional encoding anywhere (NoPE)
    # False: where window and global layers are mixed, only the window layers
    # rotate and a global layer attends by content alone (EXAONE 4.0)
    global_rope: bool = True
    # Multi-token prediction (DeepSeek-V3's module; models/hybrid.py and
    # models/mla.py: `init_mtp_params`, `mtp_logits`): how many such modules
    # the family publishes. The engine holds none: no step program runs one yet
    mtp_layers: int = 0
    # Where a sub-layer's RMSNorm sits (models/hybrid.py, the hybrid decoder's
    # three layer halves): "input": h + Mix(norm(h)) (llama); "output":
    # h + norm(Mix(h)) (OLMo 2 / OLMo 3), the same weight leaves either way
    norm_placement: str = "input"  # input | output
    # qk_norm over the WHOLE projection width, one weight vector each for q
    # and k (OLMo), instead of a head at a time over head_dim (Qwen3)
    qk_norm_whole: bool = False
    # Generation by diffusion over blocks (SDAR; models/llama.py:block_pass,
    # executor/engine.py:block_round_fn): positions lie in blocks of
    # `block_len`, a query sees every key of its own and of earlier blocks
    # (bidirectional inside a block, a prompt included), logits are UNSHIFTED
    # (a position holding `mask_token_id` predicts its own token), and a block
    # of masks is filled over denoising passes that write no cache, `block_len /
    # denoise_steps` positions due a pass by `unmask_rule`
    # ("low_confidence_dynamic": every masked position whose sampled token's
    # probability passes `unmask_threshold` where at least that many do, else
    # the due number of most confident ones; "low_confidence_static": the
    # latter alone), then committed by one pass that writes its keys and
    # values. 0 = a causal decoder that yields one token a step.
    block_len: int = 0
    denoise_steps: int = 0
    unmask_rule: str = ""  # low_confidence_dynamic | low_confidence_static
    unmask_threshold: float = 0.0
    mask_token_id: int = 0
    # serving metadata
    params_b: float = 0.0
    tie_embeddings: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    @property
    def router_width(self) -> int:
        """Experts the router scores: the published count under a share."""
        return self.n_router_experts or self.n_experts

    @property
    def lin_dv(self) -> int:
        """Value head size of the linear layers."""
        return self.lin_value_dim or self.lin_head_dim

    @property
    def recurrent(self) -> bool:
        """True when some layers keep a per-slot state of fixed size beside the
        full-length KV cache (a recurrent state, or a window layer's ring): a
        sequence is then more than its KV blocks (executor/memory.py:
        StatePool)."""
        return bool(self.gqa_layers)

    @property
    def n_attn_layers(self) -> int:
        """Layers that own rows of the KV cache."""
        return len(self.gqa_layers) if self.gqa_layers else self.n_layers

    @property
    def recurrent_kind(self) -> str:
        """The kind of the layers that are not GQA layers, which is also their
        key in the parameter tree: "ssm" (Mamba-2), "kda" (the delta rule),
        "conv" (a gated short convolution) or "win" (window attention on a
        ring)."""
        for kind, sized in (("ssm", self.ssm_heads), ("kda", self.lin_heads), ("conv", self.conv_taps)):
            if sized:
                return kind
        return "win"

    @property
    def ring_len(self) -> int:
        """Positions a window layer's ring holds a slot: the window, rounded up
        to a power of two of at least 128 (a lane tile of the scales; the
        kernels wrap by a mask). A step reads the ring BEFORE it writes, so the
        position a write replaces, `ring_len` back, is already out of the
        window and the ring needs no room beyond it."""
        return max(128, 1 << (self.sliding_window - 1).bit_length()) if self.sliding_window else 0

    @property
    def layer_period(self) -> tuple[str, ...]:
        """Kinds ("gqa" | "kda" | "ssm" | "conv" | "win") of one period of the layer
        pattern AFTER the leading dense layers (`first_dense_layers`, which the
        decoder unrolls before its scan where the feed-forward has experts);
        the rest of the stack is this period repeated (models/hybrid.py scans
        by it)."""
        rec = self.recurrent_kind
        k = self.first_dense_layers if self.n_experts else 0
        kinds = ["gqa" if i in self.gqa_layers else rec for i in range(k, self.n_layers)]
        for p in range(1, len(kinds) + 1):
            if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
                return tuple(kinds[:p])
        raise AssertionError("unreachable: p = the layers' number always matches")

    @property
    def yarn_attn_mscale(self) -> float:
        """Yarn's score-scale correction: (0.1·m·ln(factor)+1)² when
        mscale_all_dim is set (DeepSeek-V2), else 1."""
        if self.rope_factor > 1.0 and self.yarn_mscale_all_dim:
            import math

            m = 0.1 * self.yarn_mscale_all_dim * math.log(self.rope_factor) + 1.0
            return m * m
        return 1.0

    @property
    def attn_scale(self) -> float:
        if self.attn_multiplier:
            return self.attn_multiplier
        return (
            self.query_pre_attn_scalar or self.resolved_head_dim
        ) ** -0.5 * self.yarn_attn_mscale

    def param_count(self) -> int:
        """Approximate parameter count (embedding + layers + head)."""
        hd = self.resolved_head_dim
        ffn = 3 * self.dim * self.ffn_hidden
        ffn_total = self.n_layers * ffn
        if self.n_experts:
            moe_f = self.moe_ffn_hidden or self.ffn_hidden
            routed = 3 * self.dim * moe_f * self.n_experts
            shared = 3 * self.dim * moe_f * self.n_shared_experts
            moe_layer = routed + shared + self.dim * self.router_width  # + router
            k = self.first_dense_layers
            ffn_total = k * ffn + (self.n_layers - k) * moe_layer
        if self.kv_lora_rank:  # MLA factorized attention
            dn, dr, dv = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
            rq = self.q_lora_rank
            attn = (
                # q proj: dense, or down, its norm and up through the query latent
                (self.dim * rq + rq + rq * self.n_heads * (dn + dr) if rq
                 else self.dim * self.n_heads * (dn + dr))
                + self.dim * (self.kv_lora_rank + dr)  # kv down + rope key
                + self.kv_lora_rank * self.n_heads * (dn + dv)  # kv up
                + self.n_heads * dv * self.dim  # o proj
            )
            if rq:  # exact, to the parameter: the latent's norm and the selection bias
                attn += self.kv_lora_rank
                if self.n_experts and self.router_score == "sigmoid":
                    ffn_total += (self.n_layers - self.first_dense_layers) * self.router_width
        else:
            attn = (
                self.dim * self.n_heads * hd  # wq
                + 2 * self.dim * self.n_kv_heads * hd  # wk, wv
                + self.n_heads * hd * self.dim  # wo
            )
        if self.gqa_layers and self.ssm_heads:  # hybrid: GQA and Mamba-2 layers
            inner = self.ssm_heads * self.ssm_head_dim
            conv = inner + 2 * self.ssm_state  # x | B | C
            ssm = (self.dim * (inner + conv + self.ssm_heads)  # W_in: gate | x B C | dt
                   + (self.ssm_conv + 1) * conv  # the convolution and its bias
                   + 3 * self.ssm_heads  # dt_bias, A_log, D
                   + inner  # the gated norm
                   + inner * self.dim)  # W_out
            ng = len(self.gqa_layers)
            ffn_total += ng * attn + (self.n_layers - ng) * ssm  # exact: no mean a layer
            attn = 0
        elif self.gqa_layers and self.conv_taps:  # hybrid: GQA and gated short convolution layers
            conv = (self.dim * 3 * self.dim  # W_in: B | C | x
                    + self.conv_taps * self.dim  # the depthwise taps
                    + self.dim * self.dim)  # W_out
            ng = len(self.gqa_layers)
            ffn_total += ng * (attn + (2 * hd if self.qk_norm else 0)) + (self.n_layers - ng) * conv
            if self.n_experts and self.router_score == "sigmoid":  # the selection bias
                ffn_total += (self.n_layers - self.first_dense_layers) * self.router_width
            attn = 0  # exact, to the parameter: no mean a layer
        elif self.gqa_layers and self.lin_heads:  # hybrid: GQA (+ gate) layers and delta-rule layers
            hk, hv = self.lin_heads * self.lin_head_dim, self.lin_heads * self.lin_dv
            mix = (self.dim * (2 * hk + hv)  # wq, wk, wv
                   + hv * self.dim  # wo
                   + self.lin_conv * (2 * hk + hv)  # the depthwise convolutions
                   + self.dim * self.lin_heads)  # w_beta
            if self.lin_gates == "gdn":  # decay a head and output gate, full rank
                kda = mix + self.dim * self.lin_heads + self.dim * hv
            else:  # the two gates' low-rank pairs, rank = head size (models/kda.py)
                r = self.lin_head_dim
                kda = mix + 2 * (self.dim * r + r * hk)
            gqa = attn + (self.dim * self.n_heads * hd if self.attn_gate else 0)
            ng = len(self.gqa_layers)
            attn = (ng * gqa + (self.n_layers - ng) * kda) // self.n_layers
        per_layer_rest = attn + 2 * self.dim  # + norms
        if self.block_len and self.qk_norm:  # exact, to the parameter: the two head norms
            per_layer_rest += 2 * hd
        embed = self.vocab_size * self.dim
        head = 0 if self.tie_embeddings or self.arch == "encoder" else self.vocab_size * self.dim
        return embed + self.n_layers * per_layer_rest + ffn_total + head + self.dim


def periodic_windows(window: int, period: int, n_layers: int) -> tuple[int, ...]:
    """`sliding_windows` of a family that states a size and a fixed period:
    every `period`-th layer global, the rest sliding (1: every layer slides,
    Mistral; 2: alternating, Gemma2). Empty where nothing slides."""
    if not window:
        return ()
    return tuple(
        window if period == 1 or li % period != period - 1 else 0 for li in range(n_layers))


# Canonical architectures. Llama-3.1-8B per the published architecture
# (32 layers, 4096 dim, 32 heads / 8 KV heads GQA, 14336 FFN, 128k vocab,
# rope theta 5e5). The reference's catalog rows for these names carry only
# inferred metadata (tier/context_k, `04_smart_routing.sql:18-31`).
MODEL_CONFIGS: dict[str, ModelConfig] = {
    "llama-3.1-8b": ModelConfig(
        name="llama-3.1-8b",
        rope_type="llama3",
        rope_factor=8.0,
        rope_orig_max=8192,
        vocab_size=128_256,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_hidden=14_336,
        rope_theta=500_000.0,
        max_seq_len=131_072,
        params_b=8.0,
    ),
    "llama-3.2-1b": ModelConfig(
        name="llama-3.2-1b",
        rope_type="llama3",
        rope_factor=32.0,
        rope_orig_max=8192,
        vocab_size=128_256,
        dim=2048,
        n_layers=16,
        n_heads=32,
        n_kv_heads=8,
        ffn_hidden=8192,
        rope_theta=500_000.0,
        max_seq_len=131_072,
        params_b=1.24,
        tie_embeddings=True,
    ),
    # MLA (DeepSeek-style latent attention) at llama-8B-scale proportions:
    # an in-repo long-context serving config (NOT a published checkpoint) —
    # its KV cache costs 576 values/token/layer vs llama-3.1-8b's 2048, so
    # the same HBM serves ~3.6x the (slots x context). models/mla.py.
    "mla-8b": ModelConfig(
        name="mla-8b",
        arch="mla",
        vocab_size=128_256,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=1,  # latent cache: one shared row per token
        ffn_hidden=14_336,
        rope_theta=500_000.0,
        max_seq_len=131_072,
        kv_lora_rank=512,
        qk_rope_head_dim=64,
        qk_nope_head_dim=128,
        v_head_dim=128,
        params_b=9.2,
    ),
    # DeepSeek-V2-Lite — a PUBLISHED MLA+MoE checkpoint (HF
    # deepseek-ai/DeepSeek-V2-Lite config.json): dense layer 0, 26 MoE
    # layers of 64 routed + 2 shared experts, yarn rope 4k→160k. Loads via
    # models/weights.py (kv_a_proj_with_mqa / kv_b_proj / mlp.experts.* /
    # mlp.shared_experts.* mapping incl. the rope-dim de-interleave).
    "deepseek-v2-lite": ModelConfig(
        name="deepseek-v2-lite",
        arch="mla",
        vocab_size=102_400,
        dim=2048,
        n_layers=27,
        n_heads=16,
        n_kv_heads=1,
        ffn_hidden=10_944,
        norm_eps=1e-6,
        rope_theta=10_000.0,
        max_seq_len=163_840,
        kv_lora_rank=512,
        qk_rope_head_dim=64,
        qk_nope_head_dim=128,
        v_head_dim=128,
        n_experts=64,
        experts_per_tok=6,
        n_shared_experts=2,
        moe_ffn_hidden=1408,
        first_dense_layers=1,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        rope_factor=40.0,
        rope_orig_max=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        params_b=15.7,
    ),
    # tiny V2-structure config for tests: dense layer 0 + MoE layers with
    # shared experts + yarn rope — every DeepSeek-V2 mechanism at toy size.
    "tiny-v2": ModelConfig(
        name="tiny-v2",
        arch="mla",
        vocab_size=512,
        dim=128,
        n_layers=3,
        n_heads=4,
        n_kv_heads=1,
        ffn_hidden=256,
        norm_eps=1e-6,
        rope_theta=10_000.0,
        max_seq_len=512,
        kv_lora_rank=32,
        qk_rope_head_dim=16,
        qk_nope_head_dim=32,
        v_head_dim=32,
        n_experts=4,
        experts_per_tok=2,
        n_shared_experts=2,
        moe_ffn_hidden=64,
        first_dense_layers=1,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        rope_factor=4.0,
        rope_orig_max=64,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        tie_embeddings=True,
        params_b=0.002,
    ),
    "tiny-mla": ModelConfig(
        name="tiny-mla",
        arch="mla",
        vocab_size=512,
        dim=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=1,
        ffn_hidden=256,
        rope_theta=10_000.0,
        max_seq_len=512,
        kv_lora_rank=32,
        qk_rope_head_dim=16,
        qk_nope_head_dim=32,
        v_head_dim=32,
        tie_embeddings=True,
        params_b=0.001,
    ),
    # JoyAI-LLM-Flash (jdopensource/JoyAI-LLM-Flash config.json, 48B-A2.7B) as
    # ONE CHIP of a 16-way expert-parallel group, whole depth: latent attention
    # with a low-rank query in every layer, one leading dense layer, then 39
    # layers of 256 routed experts of which this chip holds experts 0-15 (the
    # router keeps its 256 columns and its 8 a token: sigmoid scores, a
    # selection bias, renormalised gates times 2.5) beside one shared expert;
    # every width, all 40 layers and all 129,280 vocabulary rows the published
    # ones. benchmark/configs/joyai-llm-flash-ep16-bf16.json lists what is assumed.
    "joyai-llm-flash-ep16": ModelConfig(
        name="joyai-llm-flash-ep16",
        arch="mla",
        vocab_size=129_280,
        dim=2048,
        n_layers=40,
        n_heads=32,
        n_kv_heads=1,  # latent cache: one shared row per token
        ffn_hidden=7168,  # the leading dense layer's
        norm_eps=1e-6,
        rope_theta=32_000_000.0,
        max_seq_len=131_072,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_rope_head_dim=64,
        qk_nope_head_dim=128,
        v_head_dim=128,
        n_experts=16,
        n_router_experts=256,
        experts_per_tok=8,
        n_shared_experts=1,
        moe_ffn_hidden=768,
        first_dense_layers=1,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        router_score="sigmoid",
        mtp_layers=1,
        params_b=4.8,
    ),
    # the same structure at toy size: a dense layer, then three expert layers
    # of 16 routed experts of which 4 are held, 2 a token, one shared expert
    "tiny-joyai": ModelConfig(
        name="tiny-joyai",
        arch="mla",
        vocab_size=512,
        dim=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=1,
        ffn_hidden=128,
        norm_eps=1e-6,
        rope_theta=10_000.0,
        max_seq_len=512,
        q_lora_rank=24,
        kv_lora_rank=32,
        qk_rope_head_dim=16,
        qk_nope_head_dim=32,
        v_head_dim=32,
        n_experts=4,
        n_router_experts=16,
        experts_per_tok=2,
        n_shared_experts=1,
        moe_ffn_hidden=32,
        first_dense_layers=1,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        router_score="sigmoid",
        mtp_layers=1,
        params_b=0.0003,
    ),
    # Solar-Open2-250B (upstage/Solar-Open2-250B config.json) as ONE CHIP of an
    # 8-way expert-parallel group, rank 0 of pipeline stage 0: one whole period
    # of the layer pattern (GQA, KDA, KDA, KDA of 48 layers), experts 0-39 of
    # the published 320 (the router keeps its 320 columns), 24,576 of 196,608
    # vocabulary rows. Every width is the published one. The low rank of the
    # two KDA gates (the head size), the sigmoid router with a selection bias
    # and the element-wise GQA gate are the family's conventions, assumed:
    # benchmark/configs/solar-open2-250b-ep8-bf16.json lists them.
    "solar-open2-250b-ep8": ModelConfig(
        name="solar-open2-250b-ep8",
        vocab_size=24_576,
        dim=4096,
        n_layers=4,
        n_heads=64,
        n_kv_heads=8,
        head_dim=128,
        ffn_hidden=10_240,  # published intermediate_size; no layer is dense
        rope_theta=10_000.0,
        norm_eps=1e-5,
        max_seq_len=1_048_576,
        n_experts=40,
        n_router_experts=320,
        experts_per_tok=8,
        n_shared_experts=1,
        moe_ffn_hidden=1280,
        norm_topk_prob=True,
        routed_scaling_factor=1.0,
        router_score="sigmoid",
        gqa_layers=(0,),
        gqa_interval=3,
        lin_heads=64,
        lin_head_dim=128,
        lin_conv=4,
        lin_neg_eigval=True,
        attn_gate=True,
        use_rope=False,
        params_b=3.3,
    ),
    # Olmo-Hybrid-7B (allenai/Olmo-Hybrid-7B config.json) cut to its first 20
    # of 32 layers (5 whole periods of three Gated DeltaNet layers and one full
    # attention layer, the period's last) with embedding and head: what one v5e
    # chip holds in bfloat16 beside 64 slots of state and int8 KV. Every width is
    # the published one. The norm placement, the whole-width q/k norm and the
    # gates' form are the family's conventions, assumed:
    # benchmark/configs/olmo-hybrid-7b-d20-bf16.json lists them.
    "olmo-hybrid-7b-d20": ModelConfig(
        name="olmo-hybrid-7b-d20",
        vocab_size=100_352,
        dim=3840,
        n_layers=20,
        n_heads=30,
        n_kv_heads=30,
        head_dim=128,
        ffn_hidden=11_008,
        rope_theta=10_000.0,  # published null; read by nothing: use_rope is False
        norm_eps=1e-6,
        max_seq_len=65_536,
        gqa_layers=(3, 7, 11, 15, 19),
        gqa_interval=3,
        lin_heads=30,
        lin_head_dim=96,
        lin_value_dim=192,
        lin_conv=4,
        lin_neg_eigval=True,
        lin_gates="gdn",
        use_rope=False,
        norm_placement="output",
        qk_norm=True,
        qk_norm_whole=True,
        params_b=4.93,
    ),
    # the same shape at toy size: two periods, heads no multiple of 8, keys and
    # values of unlike sizes, neither a multiple of 128
    "tiny-olmo-hybrid": ModelConfig(
        name="tiny-olmo-hybrid",
        vocab_size=512,
        dim=96,
        n_layers=8,
        n_heads=6,
        n_kv_heads=6,
        head_dim=16,
        ffn_hidden=192,
        norm_eps=1e-6,
        max_seq_len=512,
        gqa_layers=(3, 7),
        gqa_interval=3,
        lin_heads=6,
        lin_head_dim=24,
        lin_value_dim=48,
        lin_neg_eigval=True,
        lin_gates="gdn",
        use_rope=False,
        norm_placement="output",
        qk_norm=True,
        qk_norm_whole=True,
        params_b=0.001,
    ),
    # Granite-4.0-H-Micro (ibm-granite/granite-4.0-h-micro config.json), whole:
    # 36 Mamba-2 state-space layers and 4 attention layers (the sixth of every
    # ten) without positional encoding, a dense gated MLP in every layer, the
    # embedding table tied to the head, and Granite's four multipliers. What no
    # key of the source states is listed as `assumed` in
    # benchmark/configs/granite-4.0-h-micro-bf16.json.
    "granite-4.0-h-micro": ModelConfig(
        name="granite-4.0-h-micro",
        vocab_size=100_352,
        dim=2048,
        n_layers=40,
        n_heads=32,
        n_kv_heads=8,
        ffn_hidden=8192,
        rope_theta=10_000.0,  # published; read by nothing: use_rope is False
        norm_eps=1e-5,
        max_seq_len=131_072,
        experts_per_tok=0,
        gqa_layers=(5, 15, 25, 35),
        ssm_heads=64,
        ssm_head_dim=64,
        ssm_state=128,
        ssm_conv=4,
        use_rope=False,
        tie_embeddings=True,
        embed_multiplier=12.0,
        residual_multiplier=0.22,
        logits_divisor=8.0,
        attn_multiplier=0.015625,
        params_b=3.19,
    ),
    # the same shape at toy size: two periods of six (four state-space layers,
    # which the program scans as a run, the attention layer, one more), state-space
    # heads no multiple of 8 and two abreast in the pool (values of 64),
    # attention heads of 16
    "tiny-granite-hybrid": ModelConfig(
        name="tiny-granite-hybrid",
        vocab_size=512,
        dim=96,
        n_layers=12,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        ffn_hidden=192,
        norm_eps=1e-5,
        max_seq_len=512,
        experts_per_tok=0,
        gqa_layers=(4, 10),
        ssm_heads=6,
        ssm_head_dim=64,
        ssm_state=32,
        ssm_conv=4,
        use_rope=False,
        tie_embeddings=True,
        embed_multiplier=12.0,
        residual_multiplier=0.22,
        logits_divisor=8.0,
        attn_multiplier=0.0625,
        params_b=0.001,
    ),
    # K-EXAONE-236B-A23B (LGAI-EXAONE/K-EXAONE-236B-A23B config.json) as ONE
    # CHIP of an 8-way expert-parallel group, rank 0 of pipeline stage 0: the
    # model's first 5 of 48 layers (the leading dense layer, then one whole
    # period of the expert layers: window, window, global, window), experts
    # 0-15 of the published 128 (the router keeps its 128 columns), 19,200 of
    # 153,600 vocabulary rows. Every width is the published one. Norms on the
    # sub-layers' outputs, a q/k norm a head, no rotation on a global layer and
    # the router's selection bias are EXAONE 4.0's and DeepSeek-V3's
    # conventions, assumed: benchmark/configs/k-exaone-236b-ep8-bf16.json lists
    # them. The multi-token-prediction module (`mtp_layers`) is built where a
    # caller asks (models/hybrid.py), never by the engine.
    "k-exaone-236b-ep8": ModelConfig(
        name="k-exaone-236b-ep8",
        vocab_size=19_200,
        dim=6144,
        n_layers=5,
        n_heads=64,
        n_kv_heads=8,
        head_dim=128,
        ffn_hidden=18_432,  # the leading dense layer's
        rope_theta=1_000_000.0,
        norm_eps=1e-5,
        max_seq_len=262_144,
        n_experts=16,
        n_router_experts=128,
        experts_per_tok=8,
        n_shared_experts=1,
        moe_ffn_hidden=2048,
        first_dense_layers=1,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        router_score="sigmoid",
        gqa_layers=(3,),
        sliding_window=128,
        sliding_windows=(128, 128, 128, 0, 128),
        qk_norm=True,
        norm_placement="output",
        global_rope=False,
        mtp_layers=1,
        params_b=3.7,
    ),
    # the same shape at toy size: a dense window layer, then window, window,
    # global, window with 16 experts of which 4 are held; a ring of 128
    # positions for a window of 32
    "tiny-kexaone": ModelConfig(
        name="tiny-kexaone",
        vocab_size=512,
        dim=128,
        n_layers=5,
        n_heads=4,
        n_kv_heads=2,
        head_dim=32,
        ffn_hidden=256,
        rope_theta=10_000.0,
        max_seq_len=512,
        n_experts=4,
        n_router_experts=16,
        experts_per_tok=4,
        n_shared_experts=1,
        moe_ffn_hidden=64,
        first_dense_layers=1,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        router_score="sigmoid",
        gqa_layers=(3,),
        sliding_window=32,
        sliding_windows=(32, 32, 32, 0, 32),
        qk_norm=True,
        norm_placement="output",
        global_rope=False,
        mtp_layers=1,
        params_b=0.002,
    ),
    # LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B config.json) cut to its first 14 of 24
    # layers: the two leading dense layers, both gated short convolutions, then
    # three whole periods of (full attention, conv, conv, conv) of expert
    # layers; all 32 experts of every layer, the whole vocabulary, every width
    # the published one: stage 0 of a two-stage pipeline. One table for
    # embedding and head, heads of 64, the q/k norm a head before rope and the
    # selection bias are the released code's, assumed:
    # benchmark/configs/lfm2-8b-a1b-d14-bf16.json lists them.
    "lfm2-8b-a1b-d14": ModelConfig(
        name="lfm2-8b-a1b-d14",
        vocab_size=65_536,
        dim=2048,
        n_layers=14,
        n_heads=32,
        n_kv_heads=8,
        ffn_hidden=7168,  # the two leading dense layers'
        rope_theta=1_000_000.0,
        norm_eps=1e-5,
        max_seq_len=128_000,
        n_experts=32,
        experts_per_tok=4,
        moe_ffn_hidden=1792,
        first_dense_layers=2,
        norm_topk_prob=True,
        routed_scaling_factor=1.0,
        router_score="sigmoid",
        gqa_layers=(2, 6, 10),
        conv_taps=3,
        qk_norm=True,
        tie_embeddings=True,
        params_b=4.67,
    ),
    # the same shape at toy size: two leading dense conv layers, then two
    # periods of attention + three conv, 8 experts of which a row takes 4, all
    # held; attention heads of 64 kept
    "tiny-lfm2": ModelConfig(
        name="tiny-lfm2",
        vocab_size=512,
        dim=128,
        n_layers=10,
        n_heads=4,
        n_kv_heads=2,
        head_dim=64,
        ffn_hidden=256,
        rope_theta=1_000_000.0,
        norm_eps=1e-5,
        max_seq_len=512,
        n_experts=8,
        experts_per_tok=4,
        moe_ffn_hidden=64,
        first_dense_layers=2,
        router_score="sigmoid",
        gqa_layers=(2, 6),
        conv_taps=3,
        qk_norm=True,
        tie_embeddings=True,
        params_b=0.002,
    ),
    # the same shape at toy size: one period, 16 experts of which 4 are held
    "tiny-solar": ModelConfig(
        name="tiny-solar",
        vocab_size=512,
        dim=128,
        n_layers=4,
        n_heads=4,
        n_kv_heads=2,
        head_dim=32,
        ffn_hidden=256,
        rope_theta=10_000.0,
        max_seq_len=512,
        n_experts=4,
        n_router_experts=16,
        experts_per_tok=4,
        n_shared_experts=1,
        moe_ffn_hidden=64,
        router_score="sigmoid",
        gqa_layers=(0,),
        gqa_interval=3,
        lin_heads=4,
        lin_head_dim=32,
        lin_neg_eigval=True,
        attn_gate=True,
        use_rope=False,
        params_b=0.002,
    ),
    # Tiny config for tests / CPU dev — same code paths, toy sizes.
    "tiny-llm": ModelConfig(
        name="tiny-llm",
        vocab_size=512,
        dim=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_hidden=256,
        rope_theta=10_000.0,
        max_seq_len=512,
        params_b=0.001,
        tie_embeddings=True,
    ),
    # Mixtral 8x7B per the published architecture (32 layers, 4096 dim,
    # 32/8 GQA heads, 14336 expert FFN, 8 experts top-2, 32k vocab).
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32_000,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_hidden=14_336,
        rope_theta=1_000_000.0,
        max_seq_len=32_768,
        n_experts=8,
        experts_per_tok=2,
        params_b=46.7,
    ),
    # Tiny MoE config for tests / CPU dev — same code paths, toy sizes.
    "tiny-moe": ModelConfig(
        name="tiny-moe",
        vocab_size=512,
        dim=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_hidden=256,
        rope_theta=10_000.0,
        max_seq_len=512,
        n_experts=4,
        experts_per_tok=2,
        # E/k = 2.0 ⇒ capacity = T: dropless even at prefill, so tests can
        # assert decode == prefill == pipelined prefill bit-for-bit.
        capacity_factor=2.0,
        params_b=0.002,
        tie_embeddings=True,
    ),
    # Qwen2.5 per the published architecture: GQA with q/k/v biases,
    # untied head at 7B (tied at 0.5B), 1M rope theta, 152k vocab.
    "qwen2.5-7b": ModelConfig(
        name="qwen2.5-7b",
        vocab_size=152_064,
        dim=3584,
        n_layers=28,
        n_heads=28,
        n_kv_heads=4,
        ffn_hidden=18_944,
        rope_theta=1_000_000.0,
        norm_eps=1e-6,
        max_seq_len=32_768,
        qkv_bias=True,
        params_b=7.6,
    ),
    # Qwen3 per the published architecture (Qwen/Qwen3-8B config.json):
    # biases gone, per-head q/k RMSNorm before rope, explicit head_dim,
    # untied head at 8B.
    "qwen3-8b": ModelConfig(
        name="qwen3-8b",
        vocab_size=151_936,
        dim=4096,
        n_layers=36,
        n_heads=32,
        n_kv_heads=8,
        ffn_hidden=12_288,
        head_dim=128,
        rope_theta=1_000_000.0,
        norm_eps=1e-6,
        max_seq_len=32_768,
        qk_norm=True,
        params_b=8.2,
    ),
    # DeepSeek-R1 distills — the local deepseek models the reference's
    # smart routing seeds and tier-infers (`db/migrations/04_smart_routing
    # .sql:20,35`, `discovery.go:510` thinking-model detection). They are
    # published Qwen2.5/Llama-3.x checkpoints fine-tuned for <think>
    # reasoning, so the existing families serve them verbatim (think-tag
    # splitting: utils/tokens.py:split_think).
    "deepseek-r1-distill-qwen-1.5b": ModelConfig(
        name="deepseek-r1-distill-qwen-1.5b",
        vocab_size=151_936,
        dim=1536,
        n_layers=28,
        n_heads=12,
        n_kv_heads=2,
        ffn_hidden=8960,
        rope_theta=10_000.0,
        norm_eps=1e-6,
        max_seq_len=131_072,
        qkv_bias=True,  # Qwen2 architecture keeps attention biases
        tie_embeddings=True,
        params_b=1.78,
    ),
    "deepseek-r1-distill-llama-8b": ModelConfig(
        name="deepseek-r1-distill-llama-8b",
        vocab_size=128_256,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_hidden=14_336,
        rope_theta=500_000.0,
        max_seq_len=131_072,
        params_b=8.0,
    ),
    "qwen2.5-0.5b": ModelConfig(
        name="qwen2.5-0.5b",
        vocab_size=151_936,
        dim=896,
        n_layers=24,
        n_heads=14,
        n_kv_heads=2,
        ffn_hidden=4864,
        rope_theta=1_000_000.0,
        norm_eps=1e-6,
        max_seq_len=32_768,
        qkv_bias=True,
        tie_embeddings=True,
        params_b=0.49,
    ),
    # Mistral-7B-v0.1: llama-shaped GQA with a 4096-token sliding window on
    # every layer.
    "mistral-7b": ModelConfig(
        name="mistral-7b",
        vocab_size=32_000,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_hidden=14_336,
        rope_theta=10_000.0,
        max_seq_len=32_768,
        sliding_window=4096,
        sliding_windows=periodic_windows(4096, 1, 32),
        params_b=7.2,
    ),
    # Gemma-2-9B: gelu FFN, (1+w) RMSNorm with post-norms, sqrt(dim) embed
    # scaling, attention/logit soft-capping, alternating 4096 sliding window,
    # wide 256k tied vocab, head_dim 256.
    "gemma2-9b": ModelConfig(
        name="gemma2-9b",
        vocab_size=256_000,
        dim=3584,
        n_layers=42,
        n_heads=16,
        n_kv_heads=8,
        ffn_hidden=14_336,
        head_dim=256,
        rope_theta=10_000.0,
        norm_eps=1e-6,
        max_seq_len=8192,
        act="gelu",
        norm_weight_offset=1.0,
        embed_scale=True,
        logit_softcap=30.0,
        attn_softcap=50.0,
        sliding_window=4096,
        sliding_windows=periodic_windows(4096, 2, 42),
        query_pre_attn_scalar=224.0,  # dim / n_heads, NOT head_dim
        post_norms=True,
        tie_embeddings=True,
        params_b=9.24,
    ),
    # Tiny family configs for tests / CPU dev — same code paths, toy sizes.
    "tiny-qwen": ModelConfig(
        name="tiny-qwen",
        vocab_size=512,
        dim=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_hidden=256,
        rope_theta=10_000.0,
        max_seq_len=512,
        qkv_bias=True,
        tie_embeddings=True,
        params_b=0.001,
    ),
    "tiny-qwen3": ModelConfig(
        name="tiny-qwen3",
        vocab_size=512,
        dim=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_hidden=256,
        head_dim=64,  # explicit, != dim // n_heads = 32 (the qwen3 trap)
        rope_theta=10_000.0,
        max_seq_len=512,
        qk_norm=True,
        tie_embeddings=True,
        params_b=0.001,
    ),
    # SDAR-30B-A3B-Chat (JetLM/SDAR-30B-A3B-Chat config.json, `sdar_moe`) as ONE
    # CHIP of an 8-way expert-parallel group: Qwen3-MoE's stack (GQA 32 over 4
    # heads of 128 with q/k norm, every layer 128 softmax-routed experts of 768,
    # 8 a token, renormalised), experts 0-15 of the published 128 held (the
    # router keeps its 128 columns), every width, all 48 layers and all 151,936
    # vocabulary rows the published ones; `ffn_hidden` 6144 is stated and
    # builds nothing. It generates by diffusion over blocks of 4: the block
    # length, the steps, the unmask rule, its threshold and the mask's id are
    # the released sampler's, which the published config does not state:
    # benchmark/configs/sdar-30b-a3b-ep8-bf16.json lists them as assumed.
    "sdar-30b-a3b-ep8": ModelConfig(
        name="sdar-30b-a3b-ep8",
        vocab_size=151_936,
        dim=2048,
        n_layers=48,
        n_heads=32,
        n_kv_heads=4,
        ffn_hidden=6144,
        head_dim=128,
        rope_theta=1_000_000.0,
        norm_eps=1e-6,
        max_seq_len=32_768,
        qk_norm=True,
        n_experts=16,
        n_router_experts=128,
        experts_per_tok=8,
        moe_ffn_hidden=768,
        norm_topk_prob=True,
        router_score="softmax",
        block_len=4,
        denoise_steps=4,
        unmask_rule="low_confidence_dynamic",
        unmask_threshold=0.9,
        mask_token_id=151_669,
        params_b=5.16,
    ),
    # the same structure at toy size: three layers of 16 routed experts of
    # which 4 are held, 2 a token; the mask is the last id of the vocabulary
    "tiny-sdar": ModelConfig(
        name="tiny-sdar",
        vocab_size=512,
        dim=64,
        n_layers=3,
        n_heads=4,
        n_kv_heads=2,
        ffn_hidden=128,
        head_dim=16,
        rope_theta=10_000.0,
        norm_eps=1e-6,
        max_seq_len=512,
        qk_norm=True,
        n_experts=4,
        n_router_experts=16,
        experts_per_tok=2,
        moe_ffn_hidden=32,
        norm_topk_prob=True,
        router_score="softmax",
        block_len=4,
        denoise_steps=4,
        unmask_rule="low_confidence_dynamic",
        unmask_threshold=0.9,
        mask_token_id=511,
        params_b=0.0002,
    ),
    "tiny-mistral": ModelConfig(
        name="tiny-mistral",
        vocab_size=512,
        dim=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_hidden=256,
        rope_theta=10_000.0,
        max_seq_len=512,
        sliding_window=64,
        sliding_windows=periodic_windows(64, 1, 2),
        tie_embeddings=True,
        params_b=0.001,
    ),
    "tiny-gemma": ModelConfig(
        name="tiny-gemma",
        vocab_size=512,
        dim=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_hidden=256,
        rope_theta=10_000.0,
        max_seq_len=512,
        act="gelu",
        norm_weight_offset=1.0,
        embed_scale=True,
        logit_softcap=30.0,
        attn_softcap=50.0,
        sliding_window=64,
        sliding_windows=periodic_windows(64, 2, 2),
        query_pre_attn_scalar=24.0,  # ≠ head_dim (32) so tests exercise it
        post_norms=True,
        tie_embeddings=True,
        params_b=0.001,
    ),
    # the published nomic_bert architecture (checkpoint config.json remains
    # authoritative when a weights dir is given): full-rotary rope, post-LN
    # LayerNorm, biasless gated SwiGLU, segment embeddings, mean pooling
    "nomic-embed-text": ModelConfig(
        name="nomic-embed-text",
        arch="encoder",
        vocab_size=30_528,
        dim=768,
        n_layers=12,
        n_heads=12,
        n_kv_heads=12,
        ffn_hidden=3072,
        rope_theta=10_000.0,
        norm_eps=1e-12,
        max_seq_len=8192,
        enc_norm="layer",
        enc_post_ln=True,
        enc_gated=True,
        enc_bias=False,
        type_vocab_size=2,
        pooling="mean",
        embed_dim=768,
        params_b=0.137,
    ),
    # Qwen3-Embedding-8B is architecturally a Qwen3 CAUSAL LM (HF exports
    # Qwen3ForCausalLM) pooled at the last token — it serves through
    # EmbeddingEngine's decoder path (models/llama.py:llama_encode), so real
    # safetensors load via the ordinary qwen3 weights mapping.
    "qwen3-embedding-8b": ModelConfig(
        name="qwen3-embedding-8b",
        vocab_size=151_936,
        dim=4096,
        n_layers=36,
        n_heads=32,
        n_kv_heads=8,
        ffn_hidden=12_288,
        head_dim=128,
        rope_theta=1_000_000.0,
        norm_eps=1e-6,
        max_seq_len=32_768,
        qk_norm=True,
        tie_embeddings=True,  # encoding never touches a head
        pooling="last",
        embed_dim=4096,
        params_b=7.57,
    ),
    "tiny-embed": ModelConfig(
        name="tiny-embed",
        arch="encoder",
        vocab_size=512,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        ffn_hidden=128,
        rope_theta=10_000.0,
        max_seq_len=512,
        pooling="mean",
        embed_dim=64,
        params_b=0.0005,
    ),
}


def _compact(s: str) -> str:
    """Strip separators so "llama3.1:8b", "Llama-3.1-8B" and "llama_3.1_8b"
    all compare equal."""
    return re.sub(r"[-_.:\s]", "", s.lower())


def _encoder_config_from_hf(doc: dict, mt: str, name: str) -> ModelConfig:
    """Encoder (embedding) families: classic BERT and nomic_bert. The
    reference serves any embed model an Ollama host carries, inferring kind
    and metadata for unseen names (`discovery.go:482-560`); here an unseen
    encoder checkpoint dir becomes servable the same way."""
    import dataclasses

    if mt == "bert":
        act = str(doc.get("hidden_act") or "gelu").lower()
        if act not in ("gelu", "gelu_new", "gelu_pytorch_tanh", "relu", "silu"):
            # a silently-substituted activation would embed garbage
            raise ValueError(f"unsupported hidden_act {act!r} for bert")
        dim = int(doc["hidden_size"])
        kw = dict(
            name=name or str(doc.get("_name_or_path") or mt),
            arch="encoder",
            vocab_size=int(doc["vocab_size"]),
            dim=dim,
            n_layers=int(doc["num_hidden_layers"]),
            n_heads=int(doc["num_attention_heads"]),
            n_kv_heads=int(doc["num_attention_heads"]),
            ffn_hidden=int(doc["intermediate_size"]),
            norm_eps=float(doc.get("layer_norm_eps") or 1e-12),
            max_seq_len=int(doc.get("max_position_embeddings") or 512),
            act=act,
            enc_norm="layer",
            enc_post_ln=True,
            enc_pos="learned",
            enc_gated=False,
            enc_bias=True,
            type_vocab_size=int(doc.get("type_vocab_size") or 0),
            pooling="mean",
            embed_dim=dim,
        )
    elif mt == "nomic_bert":
        # GPT-style key names (the nomic_bert config descends from GPT2Config)
        dim = int(doc.get("n_embd") or doc.get("hidden_size") or 768)
        n_heads = int(doc.get("n_head") or doc.get("num_attention_heads") or 12)
        act = str(doc.get("activation_function") or "swiglu").lower()
        if act not in ("swiglu", "geglu", "silu", "gelu", "gelu_new", "relu"):
            raise ValueError(f"unsupported activation_function {act!r} for nomic_bert")
        if bool(doc.get("prenorm", False)):
            # prenorm nomic needs a final-norm tensor whose checkpoint
            # naming we have no fixture for — fail loud, don't guess
            raise ValueError("unsupported nomic_bert prenorm=true (post-LN only)")
        rot_frac = float(doc.get("rotary_emb_fraction", 1.0) or 0.0)
        qkv_bias = bool(doc.get("qkv_proj_bias", True))
        for bias_key in ("mlp_fc1_bias", "mlp_fc2_bias"):
            if bias_key in doc and bool(doc[bias_key]) != qkv_bias:
                # one enc_bias flag covers every linear; a checkpoint with
                # biased attention but bias-free MLP (or vice versa) would
                # load-fail or silently zero-fill — refuse up front
                raise ValueError(
                    f"unsupported nomic_bert bias split: {bias_key}="
                    f"{bool(doc[bias_key])} but qkv_proj_bias={qkv_bias}"
                )
        kw = dict(
            name=name or str(doc.get("_name_or_path") or mt),
            arch="encoder",
            vocab_size=int(doc["vocab_size"]),
            dim=dim,
            n_layers=int(doc.get("n_layer") or doc.get("num_hidden_layers") or 12),
            n_heads=n_heads,
            n_kv_heads=n_heads,
            ffn_hidden=int(doc.get("n_inner") or doc.get("intermediate_size") or 4 * dim),
            rope_theta=float(doc.get("rotary_emb_base") or 10_000.0),
            norm_eps=float(doc.get("layer_norm_epsilon") or 1e-12),
            max_seq_len=int(doc.get("n_positions") or doc.get("max_position_embeddings") or 2048),
            # swiglu → silu gate; geglu → gelu gate; plain names pass through
            act=(
                "silu" if act in ("swiglu", "silu")
                else "gelu" if act == "geglu"
                else act
            ),
            enc_norm="layer",
            # prenorm=False (the nomic default) means post-LN residuals
            enc_post_ln=not bool(doc.get("prenorm", False)),
            enc_pos="rope" if rot_frac > 0 else "learned",
            enc_gated="glu" in act,
            enc_bias=qkv_bias,
            type_vocab_size=int(doc.get("type_vocab_size") or 0),
            pooling="mean",
            embed_dim=dim,
        )
        if 0.0 < rot_frac < 1.0:
            # partial-rotary needs a split rope application the encoder does
            # not implement — refuse rather than embed garbage
            raise ValueError(
                f"unsupported rotary_emb_fraction {rot_frac} for nomic_bert "
                "(only 0.0 or 1.0)"
            )
    else:  # pragma: no cover — dispatcher only sends the two types above
        raise ValueError(f"unsupported encoder model_type {mt!r}")
    cfg = ModelConfig(**kw)
    return dataclasses.replace(cfg, params_b=round(cfg.param_count() / 1e9, 3))


def config_from_hf(doc: dict, name: str = "") -> ModelConfig:
    """Build a ModelConfig from an HF checkpoint's config.json dict.

    The reference serves ANY model name its Ollama hosts carry, inferring
    catalog metadata for names it has never seen
    (`discovery.go:482-560`); this is the in-process analog — an arbitrary
    checkpoint directory becomes servable without a hand-written entry in
    MODEL_CONFIGS. Covers the implemented decoder families plus the
    BERT-family encoders; anything else raises ValueError (a silently-wrong
    architecture would produce garbage weights-load "successes")."""
    import dataclasses

    mt = str(doc.get("model_type", "")).lower()
    if mt in ("bert", "nomic_bert"):
        return _encoder_config_from_hf(doc, mt, name)
    n_heads = int(doc.get("num_attention_heads", 32))
    kw: dict = dict(
        name=name or str(doc.get("_name_or_path") or mt or "hf-model"),
        vocab_size=int(doc["vocab_size"]),
        dim=int(doc["hidden_size"]),
        n_layers=int(doc["num_hidden_layers"]),
        n_heads=n_heads,
        n_kv_heads=int(doc.get("num_key_value_heads") or n_heads),
        ffn_hidden=int(doc["intermediate_size"]),
        head_dim=int(doc.get("head_dim") or 0),
        rope_theta=float(doc.get("rope_theta") or 10_000.0),
        norm_eps=float(doc.get("rms_norm_eps") or 1e-5),
        max_seq_len=int(doc.get("max_position_embeddings") or 8192),
        tie_embeddings=bool(doc.get("tie_word_embeddings", False)),
    )
    rs = doc.get("rope_scaling") or {}
    rs = rs if isinstance(rs, dict) else {}
    rs_type = str(rs.get("rope_type") or rs.get("type") or "").lower()
    if rs_type == "linear":
        # position interpolation (LongChat-style): uniform frequency divide
        kw.update(rope_type="linear", rope_factor=float(rs.get("factor") or 1.0),
                  rope_orig_max=int(rs.get("original_max_position_embeddings") or 1))
    if mt == "llama":
        if rs_type == "llama3":
            kw.update(
                rope_type="llama3",
                rope_factor=float(rs.get("factor") or 1.0),
                rope_orig_max=int(rs.get("original_max_position_embeddings") or 0),
                llama3_low_freq_factor=float(rs.get("low_freq_factor") or 1.0),
                llama3_high_freq_factor=float(rs.get("high_freq_factor") or 4.0),
            )
    elif mt == "qwen2":
        kw["qkv_bias"] = True
    elif mt == "qwen3":
        # biases dropped in favor of per-head q/k RMSNorm; head_dim is
        # explicit and decouples from dim // n_heads below 8B
        kw["qk_norm"] = True
    elif mt == "mistral":
        kw["sliding_window"] = int(doc.get("sliding_window") or 0)
        kw["sliding_windows"] = periodic_windows(kw["sliding_window"], 1, kw["n_layers"])
    elif mt == "mixtral":
        kw["n_experts"] = int(doc["num_local_experts"])
        kw["experts_per_tok"] = int(doc.get("num_experts_per_tok") or 2)
    elif mt == "gemma2":
        kw.update(
            act="gelu",
            norm_weight_offset=1.0,
            embed_scale=True,
            logit_softcap=float(doc.get("final_logit_softcapping") or 0.0),
            attn_softcap=float(doc.get("attn_logit_softcapping") or 0.0),
            sliding_window=int(doc.get("sliding_window") or 0),
            sliding_windows=periodic_windows(
                int(doc.get("sliding_window") or 0), 2, kw["n_layers"]),
            query_pre_attn_scalar=float(doc.get("query_pre_attn_scalar") or 0.0),
            post_norms=True,
            tie_embeddings=True,
        )
    elif mt == "deepseek_v2":
        kw.update(
            arch="mla",
            n_kv_heads=1,  # latent cache poses as one KV head (models/mla.py)
            q_lora_rank=int(doc.get("q_lora_rank") or 0),
            kv_lora_rank=int(doc["kv_lora_rank"]),
            qk_rope_head_dim=int(doc["qk_rope_head_dim"]),
            qk_nope_head_dim=int(doc["qk_nope_head_dim"]),
            v_head_dim=int(doc["v_head_dim"]),
            n_experts=int(doc.get("n_routed_experts") or 0),
            experts_per_tok=int(doc.get("num_experts_per_tok") or 2),
            n_shared_experts=int(doc.get("n_shared_experts") or 0),
            moe_ffn_hidden=int(doc.get("moe_intermediate_size") or 0),
            first_dense_layers=int(doc.get("first_k_dense_replace") or 0),
            # HF DeepseekV2Config default is False (raw softmax gates)
            norm_topk_prob=bool(doc.get("norm_topk_prob", False)),
            routed_scaling_factor=float(doc.get("routed_scaling_factor") or 1.0),
        )
        if rs_type == "yarn":
            kw.update(
                rope_type="yarn",
                rope_factor=float(rs.get("factor") or 1.0),
                rope_orig_max=int(rs.get("original_max_position_embeddings") or 0),
                yarn_beta_fast=float(rs.get("beta_fast") or 32.0),
                yarn_beta_slow=float(rs.get("beta_slow") or 1.0),
                yarn_mscale=float(rs.get("mscale") or 0.0),
                yarn_mscale_all_dim=float(rs.get("mscale_all_dim") or 0.0),
            )
    else:
        raise ValueError(
            f"unsupported HF model_type {mt!r} "
            "(supported: llama, qwen2, qwen3, mistral, mixtral, gemma2, "
            "deepseek_v2, bert, nomic_bert)"
        )
    if rs_type and kw.get("rope_factor", 1.0) <= 1.0 and rs_type != "default":
        # a scaling recipe we did not apply: serving it with plain rope
        # would silently degrade past the original context window
        raise ValueError(f"unsupported rope_scaling type {rs_type!r} for {mt!r}")
    cfg = ModelConfig(**kw)
    return dataclasses.replace(cfg, params_b=round(cfg.param_count() / 1e9, 3))


def config_from_hf_dir(path: str, name: str = "") -> ModelConfig:
    """`config_from_hf` over a checkpoint directory's config.json. For
    encoder checkpoints a sentence-transformers `1_Pooling/config.json`
    beside the weights decides the pooling mode (config.json itself never
    records it)."""
    import dataclasses
    import json as _json
    import os as _os

    with open(_os.path.join(path, "config.json")) as f:
        cfg = config_from_hf(_json.load(f), name=name)
    pool_path = _os.path.join(path, "1_Pooling", "config.json")
    if cfg.arch == "encoder" and _os.path.isfile(pool_path):
        try:
            with open(pool_path) as f:
                pdoc = _json.load(f)
            if pdoc.get("pooling_mode_cls_token"):
                cfg = dataclasses.replace(cfg, pooling="cls")
            elif pdoc.get("pooling_mode_mean_tokens"):
                cfg = dataclasses.replace(cfg, pooling="mean")
        except Exception:
            pass  # malformed pooling config: keep the family default
    return cfg


def resolve_config(model, weights_dir: str = "") -> ModelConfig:
    """Config for a model name + optional checkpoint dir. A config.json in
    the checkpoint dir is AUTHORITATIVE (it describes the actual weights);
    the name-based catalog is the fallback — so any supported-family
    checkpoint serves without a hand-written MODEL_CONFIGS entry."""
    import logging
    import os as _os

    if not isinstance(model, str):
        return model
    if weights_dir and _os.path.isfile(_os.path.join(weights_dir, "config.json")):
        try:
            return config_from_hf_dir(weights_dir, name=model)
        except Exception as e:  # any malformed config.json → catalog fallback
            logging.getLogger("models").warning(
                "config.json in %s not usable (%s); falling back to catalog "
                "entry for %r", weights_dir, e, model,
            )
    return get_config(model)


def get_config(name: str) -> ModelConfig:
    key = name.lower().strip()
    if key in MODEL_CONFIGS:
        return MODEL_CONFIGS[key]
    # Accept common aliases ("llama3.1:8b", "meta-llama/Llama-3.1-8B-Instruct")
    # by comparing separator-stripped forms of the last path segment.
    ck = _compact(key.split("/")[-1])
    for cname, cfg in MODEL_CONFIGS.items():
        cc = _compact(cname)
        if cc == ck or cc in ck:
            return cfg
    if ("deepseek-v2" in key or "deepseek_v2" in key) and "lite" in key:
        return MODEL_CONFIGS["deepseek-v2-lite"]
    if "deepseek-r1" in key or "deepseek_r1" in key or "deepscaler" in key or "deepcoder" in key:
        # Ollama-style "deepseek-r1:1.5b" etc (reference tier seeds). Size
        # decides the BASE ARCHITECTURE: 1.5b/7b are Qwen2.5 distills, 8b
        # the llama distill. Other sizes (14b/32b/70b) have no config here
        # — falling through to the KeyError beats resolving to a
        # categorically wrong family (shape-mismatched weights, wrong vocab).
        if "1.5b" in key:
            return MODEL_CONFIGS["deepseek-r1-distill-qwen-1.5b"]
        if "7b" in key:
            return MODEL_CONFIGS["qwen2.5-7b"]  # R1-Distill-Qwen-7B base arch
        if "8b" in key:
            return MODEL_CONFIGS["deepseek-r1-distill-llama-8b"]
    if "llama" in key and "1b" in key:
        return MODEL_CONFIGS["llama-3.2-1b"]
    if "llama" in key:
        return MODEL_CONFIGS["llama-3.1-8b"]
    if "qwen" in key and "0.5b" in key:
        return MODEL_CONFIGS["qwen2.5-0.5b"]
    if "qwen" in key:
        return MODEL_CONFIGS["qwen2.5-7b"]
    if "mixtral" in key:
        return MODEL_CONFIGS["mixtral-8x7b"]
    if "mistral" in key:
        return MODEL_CONFIGS["mistral-7b"]
    if "gemma" in key:
        return MODEL_CONFIGS["gemma2-9b"]
    if "embed" in key:
        return MODEL_CONFIGS["nomic-embed-text"]
    raise KeyError(f"unknown model config: {name}")
