"""Wall seconds per proved training step: from the first witness
submitted in the window to the durable commit (MANIFEST.jsonl line) of
the last proof window that started in it, over the steps those windows
proved.  Host clock."""


def read(run):
    if run.steps_proved == 0:
        return None
    return run.window_s / run.steps_proved
