"""Model FLOPs of the window's calls over the window's time, as a share
of the chip's bf16 peak.  FLOPs are counted by the configuration's
architecture module (``run.arch.generate_flops``) from its shapes:
projections, causal attention, and the output head only where logits
are produced (the prompt's last position and each decode step)."""


def read(run):
    if run.kind != "offline" or not run.calls:
        return None
    mix = run.mix
    flops = len(run.calls) * run.arch.generate_flops(
        run.m, mix["batch"], mix["prompt_len"], mix["new_tokens"])
    return 100.0 * flops / run.window_s / run.peaks["bf16_flops"]
