"""Model FLOP/s utilisation of the whole served path: the operations
behind every token stamped in the window (a first token carries its
prompt's prefill, a later one its decode step; 2 x non-embedding
parameters, attention at the token's position, the head once per served
token), over the window, the chips and the bf16 peak."""

from chipbench.flops import decode_flops, prefill_flops


def read(run):
    c = run.config
    total = 0
    for r in run.requests:
        for i, t in enumerate(r.stamps):
            if run.in_window(t):
                total += (prefill_flops(c, r.prompt_len) if i == 0
                          else decode_flops(c, r.prompt_len + i - 1))
    if not total:
        return None
    return 100.0 * total / (run.window_s * run.chips * run.peaks["bf16_flops"])
