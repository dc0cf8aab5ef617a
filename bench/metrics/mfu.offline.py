"""Model FLOPs of the window's calls over the window's time, as a share
of the chip's bf16 peak.  FLOPs are counted by ``harness.work`` from the
configuration: projections, causal attention, and the output head only
where logits are produced (the prompt's last position and each decode
step)."""
from harness import work


def read(run):
    if run.kind != "offline" or not run.calls:
        return None
    mix = run.mix
    flops = len(run.calls) * work.generate_flops(
        run.m, mix["batch"], mix["prompt_len"], mix["new_tokens"])
    return 100.0 * flops / run.window_s / run.peaks["bf16_flops"]
