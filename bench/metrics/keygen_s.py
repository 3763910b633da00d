"""Host seconds of key and generator derivation
(`repro.core.pipeline.compile`, the benchmark's span), inside set-up."""


def read(run):
    spans = run.spans.get("keygen")
    return spans[0] if spans else None
