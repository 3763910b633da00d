"""The on-chip benchmark of the zkDL prover; `bench/run.py` is its command."""
