"""Mean device time of one fused decode step of a latent-attention MoE
shard (latent paged attention, then the dense layer or the expert share,
per layer): ``fused_step_ms``'s reading of the ``jit_fused`` launches in
the trace of the window."""

from chipbench.metrics.fused_step_ms import read  # noqa: F401
