"""Model configuration shared by every assigned architecture."""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    head_dim: int = 0            # 0 => d_model // n_heads
    qk_norm: bool = False
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    # --- M-RoPE (Qwen2-VL) ---
    mrope: bool = False
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)

    # --- MoE ---
    n_experts: int = 0
    n_shared_experts: int = 0
    moe_top_k: int = 0
    d_expert: int = 0            # per-expert FFN width (0 => d_ff)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    moe_groups: int = 1          # token-dispatch groups (= data shards at scale)
    norm_topk_prob: bool = True  # renormalise the top-k gate weights to sum 1
    #: leading layers whose feed-forward is a dense SwiGLU of ``d_ff``
    #: (DeepSeek's ``first_k_dense_replace``); the rest are MoE layers
    first_dense_layers: int = 0
    #: expert ids this device holds (expert parallelism); the router still
    #: scores all ``n_experts``, and the layer returns its experts' part.
    #: Empty: every expert is held, with capacity-based dispatch
    experts_held: Tuple[int, ...] = ()

    # --- multi-head latent attention (DeepSeek-V2); kv_lora_rank 0 = off ---
    kv_lora_rank: int = 0        # latent width cached per token
    qk_nope_head_dim: int = 0    # per-head query/key width without rope
    qk_rope_head_dim: int = 0    # rope width, one key shared by every head
    v_head_dim: int = 0

    # --- YaRN rope scaling; rope_factor 1 = plain rope ---
    rope_factor: float = 1.0
    rope_orig_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0     # mscale = mscale_all_dim

    # --- SSM / hybrid ---
    ssm_state: int = 0           # mamba state size N
    d_inner: int = 0             # mamba inner width (0 => 2*d_model)
    slstm_every: int = 0         # xLSTM: every k-th block is sLSTM (0 = none)

    # --- encoder-decoder (audio) ---
    enc_layers: int = 0
    enc_seq: int = 0             # encoder source length (precomputed frames)
    d_source: int = 0            # frontend embedding dim (stub input)

    # --- VLM ---
    n_patches: int = 0           # patch embeddings per image (stub input)

    # --- attention variant ---
    window: int = 0              # 0 = full causal; >0 = sliding window

    # runtime knobs (not architecture)
    remat: bool = False          # activation checkpoint each block
    use_flash_kernel: bool = False
    #: mesh axes carrying the batch dim of activations; when set (under
    #: pjit with a mesh context) block-boundary activations are pinned to
    #: P(act_batch_axes, None, ...) so sharding propagation can't flip to
    #: replicated-batch layouts
    act_batch_axes: Tuple[str, ...] = ()
    #: sequence parallelism for recurrent (mLSTM) prefill: split the
    #: sequence into this many segments, run them in parallel over
    #: ``act_seq_axis``, and stitch with an associative state scan
    seq_segments: int = 0
    act_seq_axis: str = ""
    #: tensor-parallel mesh axis name (for keeping contracted-dim outputs
    #: sharded instead of all-reduced to full, e.g. MoE down-projection)
    act_model_axis: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.hd

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.hd

    @property
    def d_exp(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def d_in(self) -> int:
        return self.d_inner or 2 * self.d_model

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Numbers cached per token and layer under latent attention."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_held(self) -> int:
        return len(self.experts_held) or self.n_experts

    def is_moe_layer(self, layer: int) -> bool:
        return self.arch == "moe" and layer >= self.first_dense_layers

    def reduced(self, n_layers: int = 2, d_model: int = 256,
                vocab: int = 512, **kw) -> "ModelConfig":
        """Smoke-test variant of the same family (CPU-friendly)."""
        scale = d_model / self.d_model
        n_heads = max(2, min(self.n_heads, 4))
        ratio = max(1, self.n_heads // max(self.n_kv_heads, 1))
        n_kv = max(1, n_heads // min(ratio, n_heads))
        updates = dict(
            n_layers=n_layers,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=d_model // n_heads,
            d_ff=max(64, int(self.d_ff * scale) // 16 * 16) if self.d_ff else 0,
            vocab=vocab,
            enc_layers=min(self.enc_layers, 2),
            enc_seq=min(self.enc_seq, 64),
            n_patches=min(self.n_patches, 16),
            n_experts=min(self.n_experts, 4),
            n_shared_experts=min(self.n_shared_experts, 1),
            moe_top_k=min(self.moe_top_k, 2),
            d_expert=max(32, int(self.d_exp * scale) // 8 * 8) if self.n_experts else 0,
            capacity_factor=8.0 if self.n_experts else self.capacity_factor,
            d_inner=2 * d_model if self.d_inner else 0,
            ssm_state=min(self.ssm_state, 8) if self.ssm_state else 0,
            window=min(self.window, 64) if self.window else 0,
            mrope_sections=tuple(
                s * (d_model // n_heads) // self.hd for s in self.mrope_sections),
        )
        updates.update(kw)
        return replace(self, **updates)

    def _attn_params(self) -> int:
        D = self.d_model
        if self.mla:
            H, C = self.n_heads, self.kv_lora_rank
            return (D * H * (self.qk_nope_head_dim + self.qk_rope_head_dim)
                    + D * self.latent_dim + C
                    + C * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * D)
        return D * self.q_dim + 2 * D * self.kv_dim + self.q_dim * D

    def param_count(self) -> int:
        """Parameters held (for 6·N·D roofline math): per layer attention
        (q/k/v/o, or the latent projections), norms and feed-forward
        (dense, or router, held experts and shared experts); then the
        embedding, the final norm and the head."""
        D, L, V = self.d_model, self.n_layers, self.vocab
        attn = self._attn_params()
        if self.arch == "ssm":
            # mLSTM block: qkv projections + gates + out + ff
            blk = 4 * D * self.hd * self.n_heads + 2 * D
        else:
            blk = attn + 2 * D           # and the block's two norms
        if self.arch in ("hybrid",):
            d_in = self.d_in
            blk += 2 * D * d_in + d_in * (2 * self.ssm_state + 2) + d_in * D
        dense = 3 * D * self.d_ff if self.d_ff else 0
        total = L * blk + V * D * (1 if self.tie_embeddings else 2) + D
        if self.n_experts:
            n_moe = L - min(self.first_dense_layers, L)
            moe = self.n_held * 3 * D * self.d_exp + D * self.n_experts
            moe += self.n_shared_experts * 3 * D * self.d_exp
            total += n_moe * moe + (L - n_moe) * dense
        else:
            total += L * dense
        if self.enc_layers:
            total += self.enc_layers * (attn + 3 * D * self.d_ff)
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: only the routed-to experts among
        those held count, ``moe_top_k`` of ``n_experts`` on average)."""
        if not self.n_experts:
            return self.param_count()
        D = self.d_model
        n_moe = self.n_layers - min(self.first_dense_layers, self.n_layers)
        expert = 3 * D * self.d_exp
        held = n_moe * self.n_held * expert
        active = n_moe * expert * self.moe_top_k * self.n_held / self.n_experts
        return int(self.param_count() - held + active)
