"""Model FLOPs of the useful tokens of the window -- prompts ingested
and tokens emitted -- over the window's time, as a share of the chip's
bf16 peak.  Rows that a decode call computes for slots outside its
position group are not counted; the output head counts once per emitted
token."""
from harness import work


def read(run):
    if run.kind != "open_loop" or not run.loop.ticks:
        return None
    m, close = run.m, run.loop.window_s
    flops = 0.0
    for s in run.loop.served:
        p = len(s.arrival.prompt)
        for j, t in enumerate(s.token_s):
            if t > close:
                break
            flops += work.head_flops(m) + (
                work.prompt_flops(m, p) if j == 0
                else work.prompt_flops(m, 1, p + j - 1))
    return 100.0 * flops / close / run.peaks["bf16_flops"]
