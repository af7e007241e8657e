"""Mean of the build's own ``phases_s["carve_encode_write"]`` per
rebuild: carving buckets, parquet encode and write, in s."""

from perfbench.spans import builds


def read(run):
    done = [op.evidence["build"]["phases_s"]["carve_encode_write"] for op in builds(run)
            if "carve_encode_write" in op.evidence["build"].get("phases_s", {})]
    return sum(done) / len(done) if done else None
