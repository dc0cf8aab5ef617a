"""Model FLOPs of the useful tokens of the window -- prompts ingested
and tokens emitted -- over the window's time, as a share of the chip's
bf16 peak, counted by the configuration's architecture module.  Rows
that a decode call computes for slots outside its position group are
not counted; the output head counts once per emitted token."""


def read(run):
    if run.kind != "open_loop" or not run.loop.ticks:
        return None
    arch, m, close = run.arch, run.m, run.loop.window_s
    flops = 0.0
    for s in run.loop.served:
        p = len(s.arrival.prompt)
        for j, t in enumerate(s.token_s):
            if t > close:
                break
            flops += arch.head_flops(m) + (
                arch.prompt_flops(m, p) if j == 0
                else arch.prompt_flops(m, 1, p + j - 1))
    return 100.0 * flops / close / run.peaks["bf16_flops"]
