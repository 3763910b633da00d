"""Process start to ready: imports and device start, key and generator
derivation, loading (or compiling) every prover executable, the
service's warm-up prove, and the first window's training steps.  Host
clock."""


def read(run):
    return run.setup_s
