"""repro.dist: the distribution layer.

Two pieces (see ROADMAP / §3.4 of the paper):

  * :mod:`repro.dist.halo` — ``make_sharded_hdiff``: shard_map domain
    decomposition of the COSMO hdiff (depth-parallel planes + radius-2
    row halo exchange), matching the single-device kernels exactly; plus
    ``exchange_halos_2d``, the rows x cols band + diagonal-corner exchange
    behind ``repro.ir.lower_sharded``'s 2-D decomposition, the 2-axis
    ``halo_exchange_bytes`` wire model, and ``mesh_sizes``, the one reader
    of a mesh's axis sizes.
  * :mod:`repro.dist.hlo` — ``parse_collective_bytes``: the per-chip
    collective bytes of a compiled program, the measured side of the wire
    models.
"""

from repro.dist.halo import (
    call_exchange_bytes,
    exchange_halos_2d,
    exchange_row_halos,
    halo_exchange_bytes,
    halo_exchange_bytes_per_shard,
    make_sharded_hdiff,
    measured_collective_permute_bytes,
    mesh_sizes,
    owned_rows_mask,
    program_exchange_radii,
    program_halo_exchange_bytes,
    program_halo_exchange_bytes_per_shard,
    wire_drift_report,
)
from repro.dist.hlo import parse_collective_bytes

__all__ = [
    "call_exchange_bytes",
    "exchange_halos_2d",
    "exchange_row_halos",
    "halo_exchange_bytes",
    "halo_exchange_bytes_per_shard",
    "make_sharded_hdiff",
    "measured_collective_permute_bytes",
    "mesh_sizes",
    "owned_rows_mask",
    "parse_collective_bytes",
    "program_exchange_radii",
    "program_halo_exchange_bytes",
    "program_halo_exchange_bytes_per_shard",
    "wire_drift_report",
]
