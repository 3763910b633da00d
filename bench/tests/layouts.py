"""The proof layout of a geometry as the program's own arithmetic gives
it, for the tests to hold the stated layouts and the recorded proofs to."""
from bench import proofcheck


def program_layout(config: dict, steps_per_proof: int) -> dict:
    from repro.core.pipeline import PipelineConfig
    from repro.core.pipeline.graph import proof_graph_for_family
    from repro.core.pipeline.openings import gz_top_keys
    from repro.core.pipeline.tables import log2_exact

    T = steps_per_proof
    graph = proof_graph_for_family(config["family"],
                                   widths=tuple(config["widths"]),
                                   batch=config["batch"])
    cfg = PipelineConfig.from_graph(graph, q_bits=config["q_bits"],
                                    r_bits=config["r_bits"], n_steps=T)
    L = cfg.n_layers
    lay = {
        "steps": T, "x_commitments": T * cfg.batch,
        "slots": [s.name for s in graph.commit_slots],
        "openings": sorted([f"a{i}" for i in range(1, 9)]
                           + gz_top_keys(cfg)),
        "sumcheck_rounds": {f: [b.rounds for b in bs]
                            for f, bs in graph.buckets.items()},
        "pairs": {f: [T * len(b.instances) for b in bs]
                  for f, bs in graph.buckets.items()},
        "anchor_rounds": log2_exact(cfg.d_stack),
        "ipa_rounds": log2_exact(cfg.merged_len),
        # ChallengeSchedule.draw, and AnchorCoefs.draw's a1, a2, g1, g2
        "schedule_challenges": (2 * cfg.la + cfg.lw
                                + 2 * log2_exact(cfg.s_pad)
                                + log2_exact(cfg.sw_pad)),
        "anchor_challenges": T * (3 * (L - 1) + (L - 2)),
    }
    lay["bytes"] = proofcheck.layout_bytes(lay)
    return lay
