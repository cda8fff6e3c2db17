"""Single-process unit tests for repro.dist internals.

Multi-device behaviour is covered by tests/multidev/_halo_check.py (8 fake
devices, subprocess); these tests exercise the pure pieces — halo padding,
absolute-row ownership masks, the analytical wire model, the HLO
collective-byte parser that measures it, and the mesh-size reader — on the
1-device mesh so the halo logic runs in the fast tier-1 path.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import HALO, hdiff, hdiff_simple
from repro.dist import (
    exchange_halos_2d,
    exchange_row_halos,
    halo_exchange_bytes,
    halo_exchange_bytes_per_shard,
    make_sharded_hdiff,
    mesh_sizes,
    owned_rows_mask,
    parse_collective_bytes,
)
from repro.launch.mesh import make_mesh


# --- ownership masks (pure) ---------------------------------------------------


def test_owned_rows_mask_edges_and_interior():
    # 4 shards x 8 local rows = 32 global rows; global ring is 2 rows wide.
    first = np.asarray(owned_rows_mask(0, 8, 32))
    assert first.tolist() == [False, False] + [True] * 6
    last = np.asarray(owned_rows_mask(3, 8, 32))
    assert last.tolist() == [True] * 6 + [False, False]
    assert np.asarray(owned_rows_mask(1, 8, 32)).all()
    assert np.asarray(owned_rows_mask(2, 8, 32)).all()


def test_owned_rows_mask_ring_inside_one_shard():
    # 1 shard owns everything except the ring (the row_shards=1 degenerate).
    m = np.asarray(owned_rows_mask(0, 8, 8))
    assert m.tolist() == [False, False, True, True, True, True, False, False]


# --- analytical halo-wire model -----------------------------------------------


def test_halo_exchange_bytes_model():
    assert halo_exchange_bytes(64, 256, 256, row_shards=1) == 0
    # (n-1) internal boundaries x 2 directions x (depth * HALO * cols) * 4B
    assert halo_exchange_bytes(64, 256, 256, row_shards=4) == 2 * 3 * 64 * HALO * 256 * 4
    assert halo_exchange_bytes(64, 256, 256, row_shards=8) == 2 * 7 * 64 * HALO * 256 * 4
    # scales linearly in depth and cols, with itemsize
    assert halo_exchange_bytes(1, 16, 8, row_shards=2, itemsize=2) == 2 * 1 * HALO * 8 * 2


def test_halo_exchange_bytes_temporal_steps():
    """One k-step exchange round moves a k-times-deeper band; bytes per
    SIMULATED step are flat while exchange rounds (latency) divide by k."""
    one = halo_exchange_bytes(64, 256, 256, row_shards=4)
    for k in (2, 3, 4):
        per_round = halo_exchange_bytes(64, 256, 256, row_shards=4, steps=k)
        assert per_round == k * one
        assert per_round / k == one
    assert halo_exchange_bytes(64, 256, 256, row_shards=1, steps=4) == 0


def test_halo_exchange_bytes_2d_model():
    """Row bands + col bands + 4 diagonal corners; 1-shard axes free."""
    # col-only is the row formula transposed
    assert halo_exchange_bytes(64, 256, 128, 1, col_shards=4) == 2 * 3 * 64 * HALO * 256 * 4
    # full 2-D: rows + cols + corners
    got = halo_exchange_bytes(8, 64, 32, 2, halo=3, col_shards=4)
    want = (2 * 1 * 8 * 3 * 32 + 2 * 3 * 8 * 3 * 64 + 4 * 1 * 3 * 8 * 3 * 3) * 4
    assert got == want
    assert halo_exchange_bytes(8, 64, 32, 1, halo=3, col_shards=1) == 0


def test_halo_exchange_bytes_per_shard_model():
    """Per-chip permute result bytes: what parse_collective_bytes sees."""
    assert halo_exchange_bytes_per_shard(4, 16, 8, halo=2) == 2 * 4 * 2 * 8 * 4
    both = halo_exchange_bytes_per_shard(4, 16, 8, halo=2, col_sharded=True)
    assert both == (2 * 4 * 2 * 8 + 2 * 4 * 16 * 2 + 4 * 4 * 2 * 2) * 4
    assert halo_exchange_bytes_per_shard(
        4, 16, 8, row_sharded=False, col_sharded=False
    ) == 0


def test_single_shard_axes_emit_zero_collective_bytes():
    """An axis with 1 shard must SKIP its ppermutes (zero pad) instead of
    sending zero-filled halos to itself: the compiled HLO of a 1x1 mesh
    contains no collectives at all (regression for the ppermute-to-self
    fast path)."""
    mesh = make_mesh((1, 1), ("rows", "cols"))
    x = jnp.arange(2 * 6 * 6, dtype=jnp.float32).reshape(2, 6, 6)

    def exch_1d(b):
        return exchange_row_halos(b, "rows", 1)

    def exch_2d(b):
        return exchange_halos_2d(b, "rows", "cols", 1, 1)

    for fn in (exch_1d, exch_2d):
        mapped = jax.jit(
            jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=(P(None, "rows", "cols"),),
                out_specs=P(None, "rows", "cols"),
                check_vma=False,
            )
        )
        coll = parse_collective_bytes(mapped.lower(x).compile().as_text())
        assert coll["bytes"]["total"] == 0, coll
        assert not coll["counts"], coll
    # The padded result itself is the zero-rimmed block.
    out = np.asarray(
        jax.shard_map(
            exch_2d,
            mesh=mesh,
            in_specs=(P(None, "rows", "cols"),),
            out_specs=P(None, "rows", "cols"),
            check_vma=False,
        )(x)
    )
    assert out.shape == (2, 6 + 2 * HALO, 6 + 2 * HALO)
    np.testing.assert_array_equal(out[:, HALO:-HALO, HALO:-HALO], np.asarray(x))
    rim = np.ones(out.shape[1:], bool)
    rim[HALO:-HALO, HALO:-HALO] = False
    np.testing.assert_array_equal(out[:, rim], 0.0)


def test_unsharded_axes_allow_extents_thinner_than_halo():
    """A 1-shard axis sources no neighbour band — its zero pads are built at
    full halo width even when the axis extent is thinner than the halo, so
    configurations plan_partition reports feasible (e.g. 4 rows, halo 6,
    1 row shard x N col shards) lower cleanly. Only SHARDED axes enforce
    the extent >= halo band-sourcing floor."""
    out = exchange_halos_2d(jnp.ones((2, 2, 3)), None, None, 1, 1, halo=4)
    assert out.shape == (2, 2 + 8, 3 + 8)
    np.testing.assert_array_equal(np.asarray(out[:, :4]), 0.0)
    np.testing.assert_array_equal(np.asarray(out[:, 4:6, 4:7]), 1.0)
    thin = exchange_row_halos(jnp.ones((2, 1, 8)), None, 1, halo=2)
    assert thin.shape == (2, 5, 8)


def test_exchange_row_halos_rejects_fine_mesh():
    """rows/shard < halo used to silently deliver a short halo band (the
    slice clamps); it must raise instead — the single-neighbour ppermute
    cannot source a deeper band. Shape check is static: no mesh needed."""
    block = jnp.zeros((2, 1, 8))  # 1 local row
    with pytest.raises(ValueError, match="rows/shard 1 < halo"):
        exchange_row_halos(block, "row", 256)
    with pytest.raises(ValueError, match="halo"):
        exchange_row_halos(jnp.zeros((2, 3, 8)), "row", 4, halo=4)
    # boundary case rows/shard == halo is legal (shape check only here;
    # the collective itself needs a real mesh, covered in tests/multidev).


# --- halo padding semantics on the 1-device mesh ------------------------------


def test_exchange_row_halos_zero_fill_at_grid_edges():
    """With a single row shard both halos are grid edges: ppermute has no
    source, so the pads must be exactly zero (the masking contract)."""
    mesh = make_mesh((1,), ("row",))
    x = jnp.arange(2 * 4 * 3, dtype=jnp.float32).reshape(2, 4, 3)
    fn = jax.shard_map(
        lambda b: exchange_row_halos(b, "row", 1),
        mesh=mesh,
        in_specs=(P(None, "row", None),),
        out_specs=P(None, "row", None),
        check_vma=False,
    )
    out = np.asarray(fn(x))
    assert out.shape == (2, 4 + 2 * HALO, 3)
    np.testing.assert_array_equal(out[:, :HALO], 0.0)
    np.testing.assert_array_equal(out[:, -HALO:], 0.0)
    np.testing.assert_array_equal(out[:, HALO:-HALO], np.asarray(x))


def test_sharded_hdiff_on_host_mesh_matches_single_device():
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(7)
    psi = jnp.asarray(rng.standard_normal((3, 16, 12)).astype(np.float32))
    for limit, ref_fn in ((True, hdiff), (False, hdiff_simple)):
        fn = make_sharded_hdiff(mesh, depth_axis="data", row_axis="model", limit=limit)
        np.testing.assert_allclose(
            np.asarray(fn(psi)), np.asarray(ref_fn(psi, 0.025)), rtol=1e-6, atol=1e-6
        )


def test_sharded_hdiff_validates_axes_and_shapes():
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError):
        make_sharded_hdiff(mesh, depth_axis="nope")
    with pytest.raises(ValueError):
        make_sharded_hdiff(mesh, depth_axis="data", row_axis="data")
    fn = make_sharded_hdiff(mesh)
    with pytest.raises(ValueError):
        fn(jnp.zeros((4, 4)))  # rank-2: no depth dim


# --- mesh sizes -----------------------------------------------------------


def test_mesh_sizes_reads_concrete_abstract_and_duck_typed_meshes():
    """Axis sizes in declaration order from a concrete ``Mesh``, from an
    ``AbstractMesh`` (which has no ``devices``) and from any object with
    ``axis_names`` + ``devices.shape``."""
    assert mesh_sizes(make_mesh((1, 1), ("rows", "cols"))) == {"rows": 1, "cols": 1}
    abstract = jax.sharding.AbstractMesh((2, 4), ("rows", "cols"))
    assert list(mesh_sizes(abstract).items()) == [("rows", 2), ("cols", 4)]

    class Duck:
        axis_names = ("data", "model")
        devices = np.empty((8, 2), dtype=object)

    assert list(mesh_sizes(Duck()).items()) == [("data", 8), ("model", 2)]


# --- HLO collective bytes -------------------------------------------------

# One post-SPMD HLO instruction per case, with the per-chip bytes the ring
# cost model charges it: all-reduce 2B(g-1)/g, all-gather and all-to-all
# B(g-1)/g, reduce-scatter B(g-1) of its output, collective-permute B.
_HLO_CASES = {
    "all-reduce": (
        "%ar = f32[8,4]{1,0} all-reduce(f32[8,4]{1,0} %p), "
        "replica_groups={{0,1,2,3}}, to_apply=%add",
        "all-reduce", 2 * 128 * 3 / 4,
    ),
    "all-gather-iota-groups": (
        "%ag = f32[16,4]{1,0} all-gather(f32[4,4]{1,0} %p), "
        "replica_groups=[2,4]<=[8], dimensions={0}",
        "all-gather", 256 * 3 / 4,
    ),
    "reduce-scatter": (
        "%rs = f32[2,4]{1,0} reduce-scatter(f32[8,4]{1,0} %p), "
        "replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add",
        "reduce-scatter", 32 * 3,
    ),
    "all-to-all-bf16": (
        "%a2a = bf16[8,4]{1,0} all-to-all(bf16[8,4]{1,0} %p), "
        "replica_groups={{0,1}}, dimensions={0}",
        "all-to-all", 64 / 2,
    ),
    "collective-permute": (
        "%cp = f32[2,8]{1,0} collective-permute(f32[2,8]{1,0} %p), "
        "source_target_pairs={{0,1},{1,0}}",
        "collective-permute", 64,
    ),
    # Async pair: the -start tuple holds input, output and u32[] contexts;
    # the output is counted once and the -done is skipped.
    "collective-permute-async": (
        "%cps = (f32[2,8]{1,0}, f32[2,8]{1,0}, u32[], u32[]) "
        "collective-permute-start(f32[2,8]{1,0} %p), source_target_pairs={{0,1}}\n"
        "%cpd = f32[2,8]{1,0} collective-permute-done("
        "(f32[2,8]{1,0}, f32[2,8]{1,0}, u32[], u32[]) %cps)",
        "collective-permute", 64,
    ),
    "single-member-group": (
        "%ar1 = f32[8,4]{1,0} all-reduce(f32[8,4]{1,0} %p), "
        "replica_groups={{0}}, to_apply=%add",
        "all-reduce", 0.0,
    ),
}


@pytest.mark.parametrize("case", list(_HLO_CASES))
def test_parse_collective_bytes_ring_model(case):
    text, kind, want = _HLO_CASES[case]
    coll = parse_collective_bytes("HloModule m\n" + text + "\n")
    assert coll["counts"] == {kind: 1}
    assert coll["bytes"][kind] == want
    assert coll["bytes"]["total"] == want
