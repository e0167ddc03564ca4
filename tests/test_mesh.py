"""Multi-device sharding tests on the virtual 8-device CPU mesh
(conftest.py forces XLA_FLAGS=--xla_force_host_platform_device_count=8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pilosa_tpu.exec import plan
from pilosa_tpu.parallel import (
    AXIS_ROWS,
    AXIS_SLICES,
    distributed_count,
    distributed_topn,
    query_step,
    shard_planes,
    slice_mesh,
)
from pilosa_tpu.pql.parser import parse_string

W = 256  # tiny word axis: kernels are shape-agnostic


@pytest.fixture
def rng():
    return np.random.default_rng(11)


def test_slice_mesh_shape():
    m = slice_mesh(8)
    assert m.shape == {AXIS_SLICES: 8, AXIS_ROWS: 1}
    m = slice_mesh(8, row_shards=2)
    assert m.shape == {AXIS_SLICES: 4, AXIS_ROWS: 2}
    with pytest.raises(ValueError):
        slice_mesh(8, row_shards=3)


def test_shard_planes_pads(rng):
    m = slice_mesh(8)
    planes = rng.integers(0, 2**32, size=(5, 4, W), dtype=np.uint32)
    arr = shard_planes(planes, m)
    assert arr.shape == (8, 4, W)
    np.testing.assert_array_equal(np.asarray(arr)[:5], planes)
    assert not np.asarray(arr)[5:].any()


def test_distributed_count_matches_host(rng):
    m = slice_mesh(8, row_shards=2)
    q = parse_string("Union(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)), Bitmap(rowID=3))")
    expr, leaves = plan.decompose(q.calls[0])
    n_leaves = len(leaves)
    planes = rng.integers(0, 2**32, size=(8, n_leaves, 4, W), dtype=np.uint32)
    sharded = jax.device_put(
        planes, NamedSharding(m, P(AXIS_SLICES, None, AXIS_ROWS, None))
    )
    got = distributed_count(expr, sharded)
    a, b, c = planes[:, 0], planes[:, 1], planes[:, 2]
    want = int(np.bitwise_count((a & b) | c).sum())
    assert got == want


def test_distributed_topn_matches_host(rng):
    m = slice_mesh(8)
    planes = rng.integers(0, 2**32, size=(8, 16, W), dtype=np.uint32)
    src = rng.integers(0, 2**32, size=(8, W), dtype=np.uint32)
    pl = jax.device_put(planes, NamedSharding(m, P(AXIS_SLICES, AXIS_ROWS, None)))
    sr = jax.device_put(src, NamedSharding(m, P(AXIS_SLICES, None)))
    counts, ids = distributed_topn(pl, sr, 4)
    want = np.bitwise_count(planes & src[:, None, :]).sum(axis=(0, 2))
    order = np.argsort(-want, kind="stable")[:4]
    np.testing.assert_array_equal(ids, order)
    np.testing.assert_array_equal(counts, want[order])


def test_query_step_end_to_end(rng):
    """The dryrun/bench step: scatter-OR writes, fused Intersect+Count,
    TopN — one compiled program over the mesh."""
    m = slice_mesh(8, row_shards=2)
    n_slices, rows, n_upd = 8, 8, 16
    planes = rng.integers(0, 2**32, size=(n_slices, rows, W), dtype=np.uint32)
    sharded = shard_planes(planes, m)
    # Unique (row, word) targets — query_step requires pre-combined
    # duplicates (see its docstring).
    flat = rng.choice(rows * W, size=n_upd, replace=False)
    rows_upd, words_upd = flat // W, flat % W
    masks = rng.integers(0, 2**32, size=(n_slices, n_upd), dtype=np.uint32)

    step = query_step(m)
    planes2, count, top_counts, top_ids = step(
        sharded, jnp.asarray(rows_upd), jnp.asarray(words_upd), jnp.asarray(masks)
    )

    # Host reference.
    ref = planes.copy()
    for i in range(n_upd):
        ref[:, rows_upd[i], words_upd[i]] |= masks[:, i]
    np.testing.assert_array_equal(np.asarray(planes2), ref)
    want_count = int(np.bitwise_count(ref[:, 0, :] & ref[:, 1, :]).sum())
    assert int(np.asarray(count, dtype=np.int64).sum()) == want_count
    per_row = np.bitwise_count(ref & ref[:, 0:1, :]).sum(axis=(0, 2))
    order = np.argsort(-per_row, kind="stable")[:4]
    np.testing.assert_array_equal(np.asarray(top_ids), order)
    np.testing.assert_array_equal(np.asarray(top_counts), per_row[order])


def test_on_device_count_reduce_emits_collective(rng):
    """The sharded Count program carries its cross-slice reduce as a
    compiled collective (all-reduce) — only the limb pair reaches the
    host (VERDICT r1 item 3; reference analog: the HTTP fan-in reduce in
    executor.go:1176-1207)."""
    m = slice_mesh(8)
    q = parse_string("Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))")
    expr, _ = plan.decompose(q.calls[0].children[0])
    planes = np.random.default_rng(3).integers(
        0, 2**32, size=(8, 2, W), dtype=np.uint32
    )
    batch = jax.device_put(planes, NamedSharding(m, P(AXIS_SLICES, None, None)))
    fn = plan.compiled_total_count(expr, m)
    hlo = fn.lower(batch).compile().as_text()
    assert "all-reduce" in hlo, hlo[:2000]
    got = plan.recombine_count_limbs(jax.device_get(fn(batch)))
    assert got == int(np.bitwise_count(planes[:, 0] & planes[:, 1]).sum())


def test_count_reduce_collective_at_4096_slices_past_int32(rng):
    """The two-stage limb reduce keeps the collective on-device far past
    the old 2047-slice int32 cliff (VERDICT r2 item 5): 4096 slices
    still compile to one all-reduce with two scalars home.  Word count
    is scaled down (the budget math is per-slice, not per-word);
    all-ones rows make every partial exactly 2^16 — each lands entirely
    in the hi limb, the shape the old single-int32 sum mis-handled
    beyond 2047 slices at full width."""
    m = slice_mesh(8)
    q = parse_string("Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))")
    expr, _ = plan.decompose(q.calls[0].children[0])
    n, w = 4096, 2048  # 4096 slices x 65536 bits/slice, all ones
    planes = np.full((n, 2, w), 0xFFFFFFFF, dtype=np.uint32)
    planes[:7, 0, 0] = 0x1  # a little asymmetry across shards
    batch = jax.device_put(planes, NamedSharding(m, P(AXIS_SLICES, None, None)))
    fn = plan.compiled_total_count(expr, m)
    hlo = fn.lower(batch).compile().as_text()
    assert "all-reduce" in hlo, hlo[:2000]
    got = plan.recombine_count_limbs(jax.device_get(fn(batch)))
    want = int(np.bitwise_count(planes[:, 0] & planes[:, 1]).sum())
    assert want > 2**27  # ~2^28 bits: far past any single-partial scale
    assert got == want


def test_count_reduce_4d_per_slice_total_past_int32():
    """Multi-row (4-D) batches whose PER-SLICE totals pass int32 stay
    exact: the limb split happens on per-(slice,row) partials BEFORE the
    row-axis sum — a single per-slice int32 accumulator would wrap at
    2^31 (code-review regression, r3)."""
    m = slice_mesh(2)
    q = parse_string("Count(Bitmap(rowID=1))")
    expr, _ = plan.decompose(q.calls[0].children[0])
    # 2 slices x 2048 full-width rows, all ones: per-slice total is
    # exactly 2^31 — one int32 step past INT32_MAX.
    rows, w = 2048, 32768
    planes = np.full((2, 1, rows, w), 0xFFFFFFFF, dtype=np.uint32)
    sharded = jax.device_put(
        planes, NamedSharding(m, P(AXIS_SLICES, None, AXIS_ROWS, None))
    )
    got = distributed_count(expr, sharded)
    assert got == 1 << 32


def test_count_reduce_limbs_exact_past_2_31_bits():
    """Totals beyond int32 range recombine exactly from the limbs:
    2^15 slices x 2^17 bits = 2^32 bits, the budget edge (BASELINE
    configs[4] 10B-column cluster shape fits well inside)."""
    m = slice_mesh(8)
    q = parse_string("Count(Bitmap(rowID=1))")
    expr, _ = plan.decompose(q.calls[0].children[0])
    n, w = 1 << 15, 4096  # 2^15 slices x 2^17 bits, all ones
    planes = np.full((n, 1, w), 0xFFFFFFFF, dtype=np.uint32)
    batch = jax.device_put(planes, NamedSharding(m, P(AXIS_SLICES, None, None)))
    got = plan.recombine_count_limbs(
        jax.device_get(plan.compiled_total_count(expr, m)(batch))
    )
    assert got == (1 << 32)  # > int32 max; limb math must be exact


def test_distributed_topn_reduce_on_device(rng):
    """distributed_topn's cross-slice sum compiles to a collective and
    transfers only the [rows] totals."""
    from pilosa_tpu.parallel import mesh as pmesh

    m = slice_mesh(8)
    planes = rng.integers(0, 2**32, size=(8, 16, W), dtype=np.uint32)
    src = rng.integers(0, 2**32, size=(8, W), dtype=np.uint32)
    pl = jax.device_put(planes, NamedSharding(m, P(AXIS_SLICES, AXIS_ROWS, None)))
    sr = jax.device_put(src, NamedSharding(m, P(AXIS_SLICES, None)))
    fn = pmesh._topn_total_fn(m)
    hlo = fn.lower(pl, sr).compile().as_text()
    assert "all-reduce" in hlo, hlo[:2000]
    per = plan.recombine_count_limbs(jax.device_get(fn(pl, sr)))
    want = np.bitwise_count(planes & src[:, None, :]).sum(axis=(0, 2))
    np.testing.assert_array_equal(per, want)


class TestShardedExecutor:
    """The executor's multi-device path: fragments pin planes to
    slice%n_devices and query batches assemble shard-local."""

    def _exec(self, tmp_path, n_slices=8):
        import jax

        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.exec.executor import Executor
        from pilosa_tpu.ops.bitplane import SLICE_WIDTH
        from pilosa_tpu.pql.parser import parse_string

        h = Holder(str(tmp_path / "data"))
        h.open()
        idx = h.create_index("i")
        f = idx.create_frame("f")
        for s in range(n_slices):
            f.set_bit("standard", 1, s * SLICE_WIDTH + s)
            if s % 2 == 0:
                f.set_bit("standard", 2, s * SLICE_WIDTH + s)
        ex = Executor(holder=h, host="local")
        return h, ex, parse_string

    def test_fragment_planes_pinned_round_robin(self, tmp_path):
        import jax

        h, ex, parse = self._exec(tmp_path)
        devs = jax.local_devices()
        assert len(devs) == 8  # conftest virtual mesh
        seen = set()
        for s in range(8):
            frag = h.fragment("i", "f", "standard", s)
            dev = list(frag.device_plane().devices())[0]
            assert dev == devs[s % len(devs)]
            seen.add(dev)
        assert len(seen) == 8  # spread over every device

    def test_sharded_count_matches_expected(self, tmp_path):
        h, ex, parse = self._exec(tmp_path)
        q = parse('Count(Bitmap(frame="f", rowID=1))')
        assert ex.execute("i", q) == [8]
        q = parse('Count(Intersect(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=2)))')
        assert ex.execute("i", q) == [4]

    def test_sharded_row_matches_expected(self, tmp_path):
        from pilosa_tpu.net import codec
        from pilosa_tpu.ops.bitplane import SLICE_WIDTH

        h, ex, parse = self._exec(tmp_path)
        q = parse('Bitmap(frame="f", rowID=1)')
        (bm,) = ex.execute("i", q)
        assert codec.bitmap_to_json(bm)["bits"] == [
            s * SLICE_WIDTH + s for s in range(8)
        ]

    def test_uneven_groups_pad_cleanly(self, tmp_path):
        # 11 slices over 8 devices: some devices own 2 slices, some 1.
        h, ex, parse = self._exec(tmp_path, n_slices=11)
        q = parse('Count(Bitmap(frame="f", rowID=1))')
        assert ex.execute("i", q) == [11]

    def test_count_uses_on_device_total(self, tmp_path):
        """Executor Count routes through the collective total-count
        program (one scalar back to host), not per-slice device_get."""
        h, ex, parse = self._exec(tmp_path)
        before = plan._compiled_total_count.cache_info()
        q = parse('Count(Bitmap(frame="f", rowID=1))')
        assert ex.execute("i", q) == [8]
        after = plan._compiled_total_count.cache_info()
        assert after.hits + after.misses == before.hits + before.misses + 1


    def test_cold_and_warm_assembly_identical(self, tmp_path):
        """The cold (host-blocks) and warm (plane-gather) mesh batch
        assemblers share one placement helper and MUST produce identical
        pos_of layouts and batch contents for the same slice set — they
        are interchangeable producers for the same batch cache."""
        h, ex, parse = self._exec(tmp_path, n_slices=11)
        from pilosa_tpu.exec import plan as _plan

        call = parse(
            'Count(Intersect(Bitmap(frame="f", rowID=1),'
            ' Bitmap(frame="f", rowID=2)))'
        ).calls[0].children[0]
        _, leaves = _plan.decompose(call)
        slices = list(range(11))
        mesh = __import__(
            "pilosa_tpu.parallel.mesh", fromlist=["default_slices_mesh"]
        ).default_slices_mesh()
        assert mesh is not None

        cold_batch, cold_pos, cold_kept, cold_emp = (
            ex._assemble_mesh_batch_host("i", leaves, slices, mesh)
        )
        for frag in h.view("i", "f", "standard").fragments():
            frag.device_plane()
        warm_batch, warm_pos, kept, emp = ex._assemble_gather_batch(
            leaves, slices, ex._leaf_sweep("i", leaves, slices), mesh
        )

        assert cold_kept == kept and cold_emp == emp
        assert cold_pos == warm_pos
        np.testing.assert_array_equal(
            np.asarray(cold_batch), np.asarray(warm_batch)
        )


class TestShardedByDefault:
    """ISSUE 12 acceptance: with >1 device visible, mesh-sharded
    execution engages BY DEFAULT — no config required — and the
    ``[device] mesh-devices`` knob can force it off (1) or cap it."""

    def _executor(self, tmp_path, n_slices=8):
        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.exec.executor import Executor
        from pilosa_tpu.ops.bitplane import SLICE_WIDTH

        h = Holder(str(tmp_path / "data"))
        h.open()
        idx = h.create_index("i")
        f = idx.create_frame("f")
        for s in range(n_slices):
            f.set_bit("standard", 1, s * SLICE_WIDTH + s)
            f.set_bit("standard", 2, s * SLICE_WIDTH + s)
        return h, Executor(holder=h, host="local")

    def test_default_batch_is_mesh_sharded(self, tmp_path):
        from pilosa_tpu.ops import bitplane as bp
        from pilosa_tpu.parallel import mesh as pmesh
        from pilosa_tpu.pql.parser import parse_string

        assert bp.mesh_device_count() == 8  # no knob, all visible
        h, ex = self._executor(tmp_path)
        try:
            call = parse_string(
                'Count(Intersect(Bitmap(frame="f", rowID=1),'
                ' Bitmap(frame="f", rowID=2)))'
            ).calls[0].children[0]
            ent = ex._cached_batch("i", call, list(range(8)))
            assert ent["mesh"] is not None, (
                "sharded execution must engage by default with >1 device"
            )
            assert ent["mesh"] is pmesh.default_slices_mesh()
            assert len(ent["batch"].devices()) == 8
        finally:
            ex.close()
            h.close()

    def test_mesh_devices_1_forces_single_device(self, tmp_path):
        import jax

        from pilosa_tpu.ops import bitplane as bp
        from pilosa_tpu.parallel import mesh as pmesh
        from pilosa_tpu.pql.parser import parse_string

        bp.configure_mesh_devices(1)
        try:
            assert bp.mesh_device_count() == 1
            assert pmesh.default_slices_mesh() is None
            h, ex = self._executor(tmp_path)
            try:
                call = parse_string(
                    'Count(Bitmap(frame="f", rowID=1))'
                ).calls[0].children[0]
                ent = ex._cached_batch("i", call, list(range(8)))
                assert ent["mesh"] is None
                assert list(ent["batch"].devices()) == [jax.local_devices()[0]]
                q = parse_string('Count(Bitmap(frame="f", rowID=1))')
                assert ex.execute("i", q) == [8]
            finally:
                ex.close()
                h.close()
        finally:
            bp.configure_mesh_devices(0)
            pmesh._slices_mesh = None

    def test_mesh_devices_env_caps(self, monkeypatch):
        from pilosa_tpu.ops import bitplane as bp

        monkeypatch.setenv("PILOSA_DEVICE_MESH_DEVICES", "4")
        assert bp.mesh_device_count() == 4
        monkeypatch.setenv("PILOSA_DEVICE_MESH_DEVICES", "0")
        assert bp.mesh_device_count() == 8  # 0 = all visible
        # malformed values never silently disable sharding
        monkeypatch.setenv("PILOSA_DEVICE_MESH_DEVICES", "bogus")
        assert bp.mesh_device_count() == 8
        # explicit configure wins over env
        bp.configure_mesh_devices(2)
        try:
            monkeypatch.setenv("PILOSA_DEVICE_MESH_DEVICES", "4")
            assert bp.mesh_device_count() == 2
        finally:
            bp.configure_mesh_devices(0)

    def test_server_applies_mesh_devices(self, tmp_path):
        from pilosa_tpu.net.server import Server
        from pilosa_tpu.ops import bitplane as bp
        from pilosa_tpu.parallel import mesh as pmesh

        s = Server(
            data_dir=str(tmp_path / "data"),
            host="127.0.0.1:0",
            anti_entropy_interval=3600,
            polling_interval=3600,
            cache_flush_interval=3600,
            mesh_devices=1,
        )
        s.open()
        try:
            assert bp.mesh_device_count() == 1
        finally:
            s.close()
            bp.configure_mesh_devices(0)
            pmesh._slices_mesh = None

    def test_config_knob_roundtrip(self):
        from pilosa_tpu import config as config_mod

        cfg = config_mod.from_toml("[device]\nmesh-devices = 1\n")
        assert cfg.device.mesh_devices == 1
        assert "mesh-devices = 1" in cfg.to_toml()
        cfg2 = config_mod.Config()
        config_mod.apply_env(
            cfg2, {"PILOSA_DEVICE_MESH_DEVICES": "4"}
        )
        assert cfg2.device.mesh_devices == 4
        cfg2.device.mesh_devices = -1
        with pytest.raises(config_mod.ConfigError):
            cfg2.validate()


def test_total_reduce_fused_over_mesh(rng):
    """The fused multi-query "total" reduce: K distinct Count trees in
    ONE interpreter pass over a sharded batch, the cross-slice sum as a
    compiled all-reduce — only limb pairs reach the host."""
    from pilosa_tpu.ops import bitplane as bp

    m = slice_mesh(8)
    planes = rng.integers(0, 2**32, size=(8, 3, W), dtype=np.uint32)
    batch = jax.device_put(
        planes, NamedSharding(m, P(AXIS_SLICES, None, None))
    )
    em = plan.FuseEmitter(4)
    r_and = plan.lower_expr(("Intersect", ("leaf", 0), ("leaf", 1)), 0, em)
    r_or = plan.lower_expr(
        ("Union", ("leaf", 0), ("leaf", 1), ("leaf", 2)), 0, em
    )
    prog = np.zeros((8, 4), dtype=np.int32)
    prog[: len(em.rows)] = np.asarray(em.rows, dtype=np.int32)
    out_idx = np.asarray([r_and, r_or], dtype=np.int32)
    # Leaf axis pads to the emitter's bucket (4).
    padded = jax.device_put(
        np.pad(planes, ((0, 0), (0, 1), (0, 0))),
        NamedSharding(m, P(AXIS_SLICES, None, None)),
    )
    fn = plan.compiled_interp("total")
    hlo = fn.fn.lower(padded, prog, out_idx).compile().as_text()
    assert "all-reduce" in hlo, hlo[:2000]
    res = np.asarray(jax.device_get(plan.interp_exec("total", padded, prog, out_idx)))
    assert res.shape == (2, 2)
    totals = plan.recombine_count_limbs(res)
    a, b, c = planes[:, 0], planes[:, 1], planes[:, 2]
    assert int(totals[0]) == int(np.bitwise_count(a & b).sum())
    assert int(totals[1]) == int(np.bitwise_count(a | b | c).sum())


def test_mesh_shape_config_caps_devices(monkeypatch):
    from pilosa_tpu.ops import bitplane as bp
    from pilosa_tpu.parallel import mesh as pmesh

    monkeypatch.setenv("PILOSA_TPU_MESH_SHAPE", "2x2")
    assert bp.mesh_device_count() == 4
    # placement stays within the capped mesh
    import jax

    devs = jax.local_devices()[:4]
    for s in range(8):
        assert bp.home_device(s) == devs[s % 4]
    # the slices mesh respects the cap
    mesh = pmesh.default_slices_mesh()
    assert mesh is not None and mesh.devices.size == 4
    pmesh._slices_mesh = None  # reset the cached mesh for other tests
    monkeypatch.setenv("PILOSA_TPU_MESH_SHAPE", "1")
    assert bp.mesh_device_count() == 1
    # malformed / non-positive values never silently disable sharding
    for bad in ("bogus", "x", "0", "0x4", "-2"):
        monkeypatch.setenv("PILOSA_TPU_MESH_SHAPE", bad)
        assert bp.mesh_device_count() == 8, bad


def test_multihost_initialize_unconfigured_noop(monkeypatch):
    """Without JAX_COORDINATOR_ADDRESS, initialize() is a no-op (the
    configured 1-process-group path runs in a subprocess below)."""
    from pilosa_tpu.parallel import multihost

    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    multihost.initialize()
    assert multihost.global_device_count() == 8
    assert not multihost.is_multihost()


# '' = a 1-process jax.distributed group boots here; otherwise the
# error text.  Probed once per session (the boot takes seconds) so the
# multihost subprocess tests SKIP — not fail — on hosts whose jax
# build or sandbox can't form a process group at all.
_multihost_probe_result: str | None = None


def _multihost_unavailable() -> str:
    global _multihost_probe_result
    if _multihost_probe_result is not None:
        return _multihost_probe_result
    import os
    import socket
    import subprocess
    import sys

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
        JAX_NUM_PROCESSES="1",
        JAX_PROCESS_ID="0",
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", (
                "import jax; jax.config.update('jax_platforms', 'cpu')\n"
                "from pilosa_tpu.parallel import multihost\n"
                "multihost.initialize()\n"
                "assert jax.process_count() == 1\n"
                "print('probe ok')\n"
            )],
            env=env, capture_output=True, text=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        if out.returncode == 0 and "probe ok" in out.stdout:
            _multihost_probe_result = ""
        else:
            _multihost_probe_result = (out.stderr or out.stdout)[-300:]
    except subprocess.TimeoutExpired:
        _multihost_probe_result = "probe timed out"
    return _multihost_probe_result


def _require_multihost():
    err = _multihost_unavailable()
    if err:
        pytest.skip(f"jax.distributed cannot boot here: {err}")


def test_multihost_initialize_single_process_group():
    """The configured path joins a real 1-process group (subprocess:
    jax.distributed can only initialize once per process) and the second
    initialize() call is an idempotent no-op."""
    import os
    import socket
    import subprocess
    import sys

    _require_multihost()
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
        JAX_NUM_PROCESSES="1",
        JAX_PROCESS_ID="0",
    )
    out = subprocess.run(
        [sys.executable, "-c", (
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "from pilosa_tpu.parallel import multihost\n"
            "multihost.initialize()\n"
            "multihost.initialize()\n"
            "print('pc', jax.process_count())\n"
        )],
        env=env, capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-800:]
    assert "pc 1" in out.stdout



def _run_multihost_pair(tmp_path, script_text, marker):
    """Boot a REAL 2-process jax.distributed group (4 CPU devices each)
    running ``script_text``; assert both processes print ``marker <pid>
    <token>`` and return the two tokens."""
    import os
    import socket
    import subprocess
    import sys

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    script = tmp_path / "mh_worker.py"
    script.write_text(script_text)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def env_for(pid: int):
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            JAX_NUM_PROCESSES="2",
            JAX_PROCESS_ID=str(pid),
        )
        # sys.path[0] is the script's dir (tmp), not the cwd — the repo
        # needs to be importable explicitly.
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        flags = env.get("XLA_FLAGS", "")
        flags = " ".join(
            f
            for f in flags.split()
            if "xla_force_host_platform_device_count" not in f
        )
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=4".strip()
        )
        return env

    procs = [
        subprocess.Popen(
            [sys.executable, str(script)],
            env=env_for(pid),
            cwd=repo,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for pid in range(2)
    ]
    tokens = []
    for pid, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, err[-1500:]
        assert f"{marker} {pid}" in out, out
        tokens.append(out.strip().split()[-1])
    return tokens


_MULTIHOST_WORKER = """
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from pilosa_tpu.parallel import multihost
from pilosa_tpu.exec import plan
from pilosa_tpu.pql.parser import parse_string

multihost.initialize()
assert jax.process_count() == 2, jax.process_count()
devs = jax.devices()
assert len(devs) == 8, len(devs)
mesh = Mesh(np.array(devs), ('slices',))

# Same full array in every process; each contributes its local shards.
rng = np.random.default_rng(5)
planes = rng.integers(0, 2**32, size=(8, 2, 256), dtype=np.uint32)
sharding = NamedSharding(mesh, P('slices', None, None))
batch = jax.make_array_from_callback(planes.shape, sharding,
                                     lambda idx: planes[idx])

q = parse_string('Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))')
expr, _ = plan.decompose(q.calls[0].children[0])
total = plan.recombine_count_limbs(
    jax.device_get(plan.compiled_total_count(expr, mesh)(batch)))
want = int(np.bitwise_count(planes[:, 0] & planes[:, 1]).sum())
assert total == want, (total, want)
print('MH OK', jax.process_index(), total, flush=True)
"""


def test_multihost_two_process_sharded_count(tmp_path):
    """A REAL 2-process jax.distributed group (4 CPU devices each, 8
    global): the sharded Count collective crosses the process boundary
    and both processes see the oracle total (VERDICT r1 item 8;
    reference analog: multi-node server tests,
    server/server_test.go:279-374)."""
    _require_multihost()
    totals = _run_multihost_pair(tmp_path, _MULTIHOST_WORKER, "MH OK")
    assert len(set(totals)) == 1  # both processes agree on the total


_MULTIHOST_TOPN_WORKER = """
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from pilosa_tpu.parallel import multihost, mesh as pmesh

multihost.initialize()
assert jax.process_count() == 2, jax.process_count()
devs = jax.devices()
assert len(devs) == 8, len(devs)
mesh = Mesh(np.array(devs), ('slices',))

rng = np.random.default_rng(9)
planes = rng.integers(0, 2**32, size=(8, 16, 256), dtype=np.uint32)
src = rng.integers(0, 2**32, size=(8, 256), dtype=np.uint32)
p_sh = NamedSharding(mesh, P('slices', None, None))
s_sh = NamedSharding(mesh, P('slices', None))
plane = jax.make_array_from_callback(planes.shape, p_sh, lambda i: planes[i])
srcb = jax.make_array_from_callback(src.shape, s_sh, lambda i: src[i])

counts, ids = pmesh.distributed_topn(plane, srcb, 5)
want_per = np.bitwise_count(planes & src[:, None, :]).sum(axis=(0, 2))
want_ids = np.argsort(-want_per, kind='stable')[:5]
assert list(ids) == list(want_ids), (ids, want_ids)
assert list(counts) == [int(want_per[i]) for i in want_ids], counts
print('MHT OK', jax.process_index(),
      ','.join(f'{i}:{c}' for i, c in zip(ids, counts)), flush=True)
"""


def test_multihost_two_process_sharded_topn(tmp_path):
    """The distributed TopN scorer over a REAL 2-process jax.distributed
    group: the per-row cross-slice limb all-reduce crosses the process
    boundary and both processes rank identically to the numpy oracle
    (the DCN analog of the reference's TopN reduce over HTTP,
    executor.go:281-321)."""
    _require_multihost()
    tokens = _run_multihost_pair(tmp_path, _MULTIHOST_TOPN_WORKER, "MHT OK")
    # Each token is "id:count,..." — both processes must emit the same
    # ranked (id, count) sequence, already oracle-checked in-worker.
    assert len(set(tokens)) == 1
