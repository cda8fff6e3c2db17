"""repro.ir — stencil dataflow-graph IR and mini-compiler.

One program, three backends (see README "The IR subsystem"):

    graph (StencilOp DAG) --> analysis (halo / op counts, derived)
        --> lower_reference   (jnp, fused or stage-at-a-time)
        --> lower_pallas      (generic fused VMEM tile kernel)
        --> lower_sharded     (shard_map + inferred-radius halo exchange,
                               Pallas kernel composed inside the shard)

Temporal blocking rides the same pipeline: ``repeat(p, k)`` /
``StencilProgram.compose`` fuse k sweeps into one program whose chain every
backend executes per-sweep (absolute-row ring passthrough), amortising HBM
and wire round-trips k-fold per simulated step.

Multi-field programs are first-class: declare extra inputs (coefficients,
velocities) and every backend takes a ``{field: array}`` mapping. Halos,
reads and wire bytes derive PER FIELD (``field_radii`` / ``reads_by_field``)
and sum — the Pallas kernel sizes each field's row halo by its own radius,
and the sharded lowering skips the exchange for radius-0 fields.
``vadvc_program`` / ``hdiff_coupled_program`` are the shipped workloads.

Multi-OUTPUT programs (coupled PDE systems) declare ``outputs={field:
op_name, ...}`` — several fields evolve per sweep, each with its own
derived radius/footprint, and every backend returns ``{field: array}``.
One fused kernel writes all outputs; the sharded lowering moves all
evolving halos in ONE merged exchange per k sweeps.
``shallow_water_program`` (u, v, h gravity-wave coupling) and
``advection_diffusion_program`` (evolving u, c over a shared v) are the
shipped coupled systems.

Ensemble batching rides every backend: ``lower_batched`` vmaps a lowering
over a leading member axis (one compiled kernel for N perturbed initial
conditions, bit-identical to N independent applications), composing with
the (R, C) mesh of the sharded backends — the forecast-serving layer's
execution path (``repro.serve``).

Autodiff is one more graph transform: ``adjoint(p)`` derives the cotangent
program from the same DAG (transposed access offsets, reversed op chain,
nonlinear combinators linearized around ``c~``-cached primal values —
adjoint radii equal primal radii, field by field) and ``build_backend(...,
differentiable=True)`` attaches it as a ``jax.custom_vjp`` through the SAME
backend: the Pallas backward is its own fused kernel, the sharded backward
reuses the ``exchange_radii()``-driven halo exchange (``repro.ir.autodiff``).

This package is self-contained (no imports from other ``repro`` modules at
import time), so ``repro.core`` and ``repro.kernels`` derive their specs and
tile plans from it without cycles.
"""

from repro.ir.graph import (
    Offset,
    OpCost,
    ProgramSpec,
    Read,
    StencilOp,
    StencilProgram,
    repeat,
)
from repro.ir.ops import affine, flux, product, scaled_residual, weighted_residual
from repro.ir.programs import (
    ELEMENTARY_PROGRAMS,
    MULTIFIELD_PROGRAMS,
    MULTIOUTPUT_PROGRAMS,
    advection_diffusion_program,
    hdiff_coupled_program,
    hdiff_fields_program,
    hdiff_multistep_program,
    hdiff_program,
    jacobi1d_program,
    jacobi2d_3pt_program,
    jacobi2d_5pt_program,
    jacobi2d_9pt_program,
    laplacian_program,
    seidel2d_program,
    shallow_water_program,
    smagorinsky_coeff,
    vadvc_program,
)
from repro.ir.evaluate import (
    apply_program,
    embed_interior,
    interior_eval,
    interior_eval_multi,
    interior_region,
    resolve_field_arrays,
    ring_crop,
    slab_step,
    slab_sweep,
    thread_chain,
)
from repro.ir.plan import (
    DEFAULT_VMEM_TILE_BUDGET,
    VMEM_BUDGET_ENV,
    Partition2D,
    pick_block_rows,
    plan_partition,
    vmem_tile_budget,
)
from repro.ir.lower_reference import lower_reference
from repro.ir.lower_pallas import lower_pallas
from repro.ir.lower_sharded import lower_sharded
from repro.ir.lower_batched import BATCHED_BACKENDS, build_backend, lower_batched
from repro.ir.autodiff import (
    acc_field,
    adjoint,
    augmented_forward,
    cache_field,
    cache_fields,
    differentiable_lowering,
    make_vjp,
    pad_widths,
    seed_field,
)
