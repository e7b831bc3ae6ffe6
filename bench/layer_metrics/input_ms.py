"""Mean host time per window step in the benchmark's batch source: picking
the pooled batch and placing it on the tile mesh (``TiledCNNArch.place_batch``)."""


def read(run):
    spent = run.result["window"]["source_s"]
    return 1e3 * sum(spent) / len(spent) if spent else None
