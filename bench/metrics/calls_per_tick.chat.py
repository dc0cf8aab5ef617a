"""Decode-step calls the batcher made per scheduler tick in the window:
one per distinct slot position (counted at the batcher's step callable,
program counter)."""


def read(run):
    if run.kind != "open_loop" or not run.loop.ticks:
        return None
    return run.loop.decode_calls / run.loop.ticks
