"""Rows routed to a held expert per MoE layer per fused decode call: the
mean, over the window's ``engine.fused`` spans that carry them, of every
MoE layer's rows per held expert (the span's ``expert_rows``, which the
engine records while the program's tracer is on)."""

from chipbench.phases import in_window


def read(run):
    rows = [n for r in in_window(run, "engine.fused")
            for layer in r.attrs.get("expert_rows", ()) for n in layer]
    return sum(rows) / len(rows) if rows else None
