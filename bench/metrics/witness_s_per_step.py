"""Host seconds of the integer training step that makes each witness
(the benchmark's span around `build_zkdl_step`'s step), per step
trained in the run."""


def read(run):
    spans = run.spans.get("train_step")
    return sum(spans) / len(spans) if spans else None
