"""Prompt plus generated tokens of every call in the window, over the
window: first call's start to the end of the last call started before
the window's seconds ran out (host clock)."""


def read(run):
    if run.kind != "offline" or not run.calls:
        return None
    tokens = sum(c.prompt_tokens + c.new_tokens for c in run.calls)
    return tokens / run.window_s
