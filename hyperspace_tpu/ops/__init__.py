from hyperspace_tpu.ops.hashing import bucket_ids, combine_hashes, hash_int_column, string_dict_hashes

#: Every Pallas kernel the package ships, by its jit call-site key
#: (static-analysis rule HSL026, analysis/tracedomain.py — the mirror
#: of ``faults.KNOWN_POINTS``). Each declared kernel's engagement chain
#: must statically carry the full eligibility ladder: an explicit
#: shape/dtype rule, both ``device.kernel.*`` counters, and no broad
#: ``except`` that swallows a lowering error. Undeclared engagements and
#: stale entries are findings, so this tuple is provably the complete
#: kernel inventory.
KNOWN_KERNELS = (
    "ops.sortkeys.pallas_run_bounds",
    "ops.topk.pallas_tile",
)

__all__ = [
    "KNOWN_KERNELS",
    "bucket_ids",
    "combine_hashes",
    "hash_int_column",
    "string_dict_hashes",
]
