"""TPU kernel substrate.

Vectorized primitives beneath the CC layer: key hashing, device-side
workload sampling, duplicate-scatter resolution, and the conflict-matrix /
serialization-sweep kernels that replace the reference's per-row latched
managers (`concurrency_control/*`, dispatched from `storage/row.cpp:197-310`).
"""

from deneva_tpu.ops.hashing import bucket_hash, combine_key  # noqa: F401
from deneva_tpu.ops.sampling import HotSet, Zipfian, uniform_keys  # noqa: F401
from deneva_tpu.ops.scatter import (compact_winners,  # noqa: F401
                                    last_writer, scatter_winner_rows)
from deneva_tpu.ops.forward import (ForwardPlan,  # noqa: F401
                                    commit_all_verdict, forward_plan,
                                    forward_plan_flat, forward_verdict,
                                    forwarding_applies,
                                    last_earlier_writer, mc_defer_verdict,
                                    mc_pair_cap, mc_plan_defer)
from deneva_tpu.ops.gather import checksum_needed_rows  # noqa: F401
from deneva_tpu.ops.conflict import (  # noqa: F401
    access_incidence,
    key_overlap,
    earlier_edges,
    greedy_first_fit,
    wavefront_levels,
    precedence_levels,
)
