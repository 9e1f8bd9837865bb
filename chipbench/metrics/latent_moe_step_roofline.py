"""Share of its roofline that the latent-attention MoE fused decode step
reaches: ``fused_step_roofline``'s reading, whose operations and bytes
per launch come from the family's ``fused_step`` on the call's live
lengths (for ``deepseek_v2``: the shard's weights, of each MoE layer's
held experts only those the live rows are expected to reach, and the
live rows' latent cache)."""

from chipbench.metrics.fused_step_roofline import read  # noqa: F401
