"""Prompt tokens prefilled in the window (the valid rows of every chunk
step that ended in it, padding left out), per second of the window."""


def read(run):
    n = sum(a.get("valid", 0) for _, _, a in run.window_spans("prefill_chunk"))
    n += sum(a.get("prompt_len", 0)
             for _, _, a in run.window_spans("full_prefill"))
    return n / run.span_s
