"""Mean of the build's own ``phases_s["decode"]`` per rebuild: the
parquet decode of the source columns, in s."""

from perfbench.spans import builds


def read(run):
    done = [op.evidence["build"]["phases_s"]["decode"] for op in builds(run)
            if "decode" in op.evidence["build"].get("phases_s", {})]
    return sum(done) / len(done) if done else None
