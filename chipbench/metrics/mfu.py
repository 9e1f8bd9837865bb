"""Model FLOP/s utilisation of the whole served path: the operations
behind every token stamped in the window (a first token carries its
prompt's prefill, a later one its decode step, each as the config's
family counts it), over the window, the chips and the bf16 peak."""


def read(run):
    fam, c = run.family, run.config
    total = 0
    for r in run.requests:
        for i, t in enumerate(r.stamps):
            if run.in_window(t):
                total += (fam.prefill_flops(c, r.prompt_len) if i == 0
                          else fam.decode_flops(c, r.prompt_len + i - 1))
    if not total:
        return None
    return 100.0 * total / (run.window_s * run.chips * run.peaks["bf16_flops"])
