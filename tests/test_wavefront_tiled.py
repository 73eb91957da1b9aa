"""Tiled wavefront + scan twin: parity at adversarial band geometries.

The PR-10 contract: every execution mode of the wavefront DP — the banded
VMEM-blocked Pallas kernel at ANY tile setting, and the compiled
``lax.scan`` twin — produces bit-identical distances, hit sets, and
fused-ε prune certificates to the single-band (untiled) schedule and the
numpy host oracle, across ragged batches, all four alignment distances,
and multi-dim series.  Plus the policy plumbing: the ``REPRO_INTERPRET``
/ ``REPRO_KERNEL_EXEC`` env overrides, the ``default_tile`` VMEM
heuristic, the extended jit-cache key (zero retrace per
``(exec, tile)`` shape class), ``pairwise_l2``'s policy routing, and the
``RetrievalConfig`` fields that carry ``kernel_exec`` / ``kernel_tile``
down through the engines.
"""

import numpy as np
import pytest

from repro.distances import get, np_backend
from repro.kernels import dispatch, ops, registry
from repro.kernels.wavefront import band_layout

MODES4 = ["dtw", "erp", "frechet", "levenshtein"]

RNG = np.random.default_rng(13)


def _ragged(name, B, Lx, Ly, rng, d=2):
    lx = rng.integers(1, Lx + 1, B)
    ly = rng.integers(1, Ly + 1, B)
    if get(name).string:
        xs = rng.integers(0, 6, size=(B, Lx))
        ys = rng.integers(0, 6, size=(B, Ly))
    else:
        xs = rng.normal(size=(B, Lx, d)).astype(np.float32)
        ys = rng.normal(size=(B, Ly, d)).astype(np.float32)
    for i in range(B):
        xs[i, lx[i]:] = 0
        ys[i, ly[i]:] = 0
    return xs, ys, lx, ly


def _eps_mid(name, xs, ys, lx, ly):
    """A threshold strictly between achieved distances: stable verdicts."""
    want = np_backend.batch_for(name)(xs, ys, lx, ly)
    u = np.unique(want[np.isfinite(want)])
    return float(u[: max(2, len(u) // 2)].mean()) if len(u) > 1 \
        else float(u[0]) + 0.5


def _assert_same(got, base, ctx):
    np.testing.assert_array_equal(got.dist, base.dist, err_msg=ctx)
    np.testing.assert_array_equal(got.hit, base.hit, err_msg=ctx)
    np.testing.assert_array_equal(got.pruned, base.pruned, err_msg=ctx)


# -- band layout -------------------------------------------------------------


def test_band_layout_windows_match_full_slices():
    """Each band tile holds exactly the reversed-y stretch its diagonals
    read; clipped (pre-sequence) reads only ever feed masked cells, but
    the in-range part must be a verbatim copy."""
    rng = np.random.default_rng(0)
    Lx, Ly, T = 7, 6, 3
    Ypad = 2 * Lx + Ly + 1
    y = rng.normal(size=(2, Ypad, 3)).astype(np.float32)
    bands = np.asarray(band_layout(y, Lx, Ly, T))
    K = Lx + Ly
    nbands = -(-K // T)
    Wb = Lx + T
    # band axis leads: every block's last two dims are the full (Wb, d)
    assert bands.shape == (nbands, 2, Wb, 3)
    for j in range(nbands):
        o = Lx + 1 + Ly - (j + 1) * T
        tile_j = bands[j]
        lo = max(0, o)
        np.testing.assert_array_equal(
            tile_j[:, lo - o:], y[:, lo:o + Wb],
            err_msg=f"band {j} window drift")


# -- tiled / scan parity at adversarial band geometries ----------------------


@pytest.mark.parametrize("name", MODES4)
def test_tiled_parity_all_tiles_ragged(name):
    """dist/hit/pruned bit-identical across every band depth, the scan
    twin, and the numpy oracle — ragged rows spread across bands."""
    rng = np.random.default_rng(21)
    B, Lx, Ly = 9, 7, 6
    K = Lx + Ly
    xs, ys, lx, ly = _ragged(name, B, Lx, Ly, rng)
    eps = _eps_mid(name, xs, ys, lx, ly)
    spec = registry.get(name)
    eps_v = np.full(B, eps, np.float32)

    base = spec.batch(xs, ys, lx, ly, eps=eps_v, exec="pallas", tile=K)
    want = np_backend.batch_for(name)(xs, ys, lx, ly)
    np.testing.assert_array_equal(base.hit, want <= eps)
    np.testing.assert_allclose(base.dist[base.hit], want[base.hit],
                               rtol=1e-4, atol=1e-4)
    assert not base.pruned[base.hit].any()

    # len == tile, tile +- 1, one band, many bands, heuristic
    for tile in (1, 3, Lx, Ly, K - 1, K + 1, None):
        got = spec.batch(xs, ys, lx, ly, eps=eps_v,
                         exec="pallas", tile=tile)
        _assert_same(got, base, f"{name} tile={tile}")
    got = spec.batch(xs, ys, lx, ly, eps=eps_v, exec="scan")
    _assert_same(got, base, f"{name} scan")


@pytest.mark.parametrize("name", ["dtw", "erp"])
def test_tiled_parity_multidim_no_eps(name):
    """d=3 series, no ε: full distances equal across modes and tiles."""
    rng = np.random.default_rng(8)
    B, Lx, Ly = 6, 10, 9
    xs, ys, lx, ly = _ragged(name, B, Lx, Ly, rng, d=3)
    spec = registry.get(name)
    base = spec.batch(xs, ys, lx, ly, exec="pallas", tile=Lx + Ly)
    want = np_backend.batch_for(name)(xs, ys, lx, ly)
    np.testing.assert_allclose(base.dist, want, rtol=1e-4, atol=1e-4)
    for tile in (4, 5, Lx + Ly - 1):
        got = spec.batch(xs, ys, lx, ly, exec="pallas", tile=tile)
        _assert_same(got, base, f"{name} d=3 tile={tile}")
    _assert_same(spec.batch(xs, ys, lx, ly, exec="scan"), base,
                 f"{name} d=3 scan")


def test_tiled_parity_row_boundary_coincidences():
    """Rows whose answer diagonal lands exactly ON a band boundary (and
    one diagonal either side) — the ε-certificate-at-band-boundary rule
    must not leak verdicts early or late."""
    rng = np.random.default_rng(5)
    B, Lx, Ly, T = 6, 6, 6, 4
    xs = rng.normal(size=(B, Lx, 2)).astype(np.float32)
    ys = rng.normal(size=(B, Ly, 2)).astype(np.float32)
    # target diagonals lx+ly = 7, 8, 9 straddle the j=1 band end (8)
    lx = np.array([3, 4, 4, 4, 5, 6])
    ly = np.array([4, 4, 5, 4, 4, 3])
    for i in range(B):
        xs[i, lx[i]:] = 0
        ys[i, ly[i]:] = 0
    spec = registry.get("dtw")
    eps_v = np.full(B, _eps_mid("dtw", xs, ys, lx, ly), np.float32)
    base = spec.batch(xs, ys, lx, ly, eps=eps_v,
                      exec="pallas", tile=Lx + Ly)
    got = spec.batch(xs, ys, lx, ly, eps=eps_v, exec="pallas", tile=T)
    _assert_same(got, base, "boundary-coincident rows")
    _assert_same(spec.batch(xs, ys, lx, ly, eps=eps_v, exec="scan"),
                 base, "boundary-coincident rows (scan)")


def test_packed_dispatch_scan_matches_pallas_ragged():
    """The packed ragged-bucket dispatcher carries exec/tile through the
    bucket sort + scatter unchanged."""
    rng = np.random.default_rng(31)
    xs, ys, lx, ly = _ragged("erp", 11, 9, 7, rng)
    eps = _eps_mid("erp", xs, ys, lx, ly)
    base = dispatch.packed_batch("erp", xs, ys, lx, ly, eps=eps)
    for kw in (dict(exec="scan"), dict(exec="pallas", tile=3),
               dict(exec="pallas", tile=5)):
        got = dispatch.packed_batch("erp", xs, ys, lx, ly, eps=eps, **kw)
        _assert_same(got, base, f"packed {kw}")


# -- jit-cache discipline: (exec, tile) are key axes, zero retrace -----------


def test_no_retrace_per_exec_tile_shape_class():
    registry.clear_cache()
    rng = np.random.default_rng(3)
    xs = rng.normal(size=(8, 6, 2)).astype(np.float32)
    ys = rng.normal(size=(8, 7, 2)).astype(np.float32)
    spec = registry.get("dtw")
    variants = [dict(exec="pallas", tile=4), dict(exec="pallas", tile=5),
                dict(exec="scan")]
    traces_at = []
    for kw in variants:
        spec.batch(xs, ys, **kw)
        traces_at.append(registry.STATS["traces"])
    # distinct (exec, tile) classes each compiled something new
    assert traces_at[0] < traces_at[1] < traces_at[2]
    t0 = registry.STATS["traces"]
    for kw in variants:
        spec.batch(xs * 2.0, ys - 1.0, **kw)   # same shapes, new values
    assert registry.STATS["traces"] == t0, "warm tiled/scan sweep retraced"


def test_ops_wavefront_threads_exec_and_tile():
    rng = np.random.default_rng(9)
    xs = rng.normal(size=(5, 6, 2)).astype(np.float32)
    ys = rng.normal(size=(5, 6, 2)).astype(np.float32)
    base = ops.wavefront(xs, ys, "dtw", interpret=True)
    for kw in (dict(exec="scan"), dict(exec="pallas", tile=3)):
        got = ops.wavefront(xs, ys, "dtw", interpret=True, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


# -- policy plumbing: env overrides, heuristic, pairwise_l2 ------------------


def test_repro_interpret_env_override(monkeypatch):
    prev = registry.set_default_interpret(None)
    try:
        monkeypatch.setenv("REPRO_INTERPRET", "0")
        registry.set_default_interpret(None)     # force re-resolution
        assert registry.default_interpret() is False
        monkeypatch.setenv("REPRO_INTERPRET", "yes")
        registry.set_default_interpret(None)
        assert registry.default_interpret() is True
        # the hook wins over the env var, and returns the previous pin
        assert registry.set_default_interpret(False) is True
        assert registry.default_interpret() is False
    finally:
        registry.set_default_interpret(prev)


def test_repro_kernel_exec_env_override(monkeypatch):
    prev = registry.set_default_exec(None)
    try:
        monkeypatch.setenv("REPRO_KERNEL_EXEC", "scan")
        registry.set_default_exec(None)
        assert registry.default_exec() == "scan"
        monkeypatch.setenv("REPRO_KERNEL_EXEC", "bogus")
        registry.set_default_exec(None)
        with pytest.raises(ValueError, match="REPRO_KERNEL_EXEC"):
            registry.default_exec()
        monkeypatch.delenv("REPRO_KERNEL_EXEC")
        registry.set_default_exec(None)
        assert registry.default_exec() == "pallas"
        with pytest.raises(ValueError, match="exec mode"):
            registry.set_default_exec("bogus")
        with pytest.raises(ValueError, match="exec mode"):
            registry.resolve_exec("bogus")
    finally:
        registry.set_default_exec(prev)


def test_default_tile_heuristic_bounds():
    # small shapes: one band (the untiled schedule — CI baselines stable)
    for Lx, Ly, d in [(6, 6, 1), (12, 12, 2), (20, 20, 3)]:
        assert registry.default_tile(Lx, Ly, d) == Lx + Ly
    # the clamp floor and ceiling hold everywhere, and the tile shrinks
    # monotonically as the budget tightens
    t_big = registry.default_tile(4096, 4096, 8)
    t_small = registry.default_tile(4096, 4096, 8, budget=1 << 16)
    assert 8 <= t_small <= t_big <= 8192
    assert t_small == 8        # starved budget bottoms out at the floor
    assert t_big < 8192        # long wide segments really do get banded


def test_pairwise_l2_follows_interpret_policy():
    from repro.kernels.pairwise_l2 import pairwise_l2_pallas
    from repro.kernels.ref import pairwise_l2_ref
    rng = np.random.default_rng(2)
    x = rng.normal(size=(8, 3)).astype(np.float32)
    y = rng.normal(size=(8, 3)).astype(np.float32)
    want = np.asarray(pairwise_l2_ref(x, y))
    # explicit override and policy default agree (policy resolves to
    # interpret=True off-TPU)
    got_explicit = np.asarray(
        pairwise_l2_pallas(x, y, bm=8, bn=8, interpret=True))
    got_policy = np.asarray(pairwise_l2_pallas(x, y, bm=8, bn=8))
    np.testing.assert_allclose(got_explicit, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got_policy, got_explicit)


# -- config / engine plumbing ------------------------------------------------


def test_config_validates_and_roundtrips_kernel_exec_tile():
    from repro.retrieval import RetrievalConfig
    cfg = RetrievalConfig("dtw", index="linear", kernel_backend="pallas",
                          kernel_exec="scan", kernel_tile=6)
    again = RetrievalConfig.from_json(cfg.to_json())
    assert again == cfg
    assert again.kernel_exec == "scan" and again.kernel_tile == 6
    with pytest.raises(ValueError, match="kernel_exec"):
        RetrievalConfig("dtw", index="linear", kernel_exec="bogus")
    with pytest.raises(ValueError, match="kernel_tile"):
        RetrievalConfig("dtw", index="linear", kernel_tile=0)


@pytest.mark.parametrize("kw", [dict(kernel_exec="scan"),
                                dict(kernel_tile=4),
                                dict(kernel_exec="scan", kernel_tile=4)])
def test_window_mode_scan_and_tile_match_host(kw):
    """Facade-level: hit sets AND eval counts identical to the host loop
    when the engines run the scan backend / an explicit band depth."""
    from repro.retrieval import RetrievalConfig, Retriever
    rng = np.random.default_rng(17)
    steps = rng.normal(scale=0.3, size=(40, 8, 2))
    data = np.cumsum(steps, axis=1) + rng.normal(size=(40, 1, 2))
    queries = [data[i][:ln] for i, ln in zip((3, 11, 27), (6, 8, 7))]
    host = Retriever.build(
        RetrievalConfig("dtw", index="linear"), data)
    want = host.batch(queries).via("host").range(1.0)
    dev = Retriever.build(
        RetrievalConfig("dtw", index="linear", kernel_backend="pallas",
                        **kw), data)
    got = dev.batch(queries).via("batched").range(1.0)
    assert got.hits == want.hits, f"{kw} hit-set drift"
    assert got.stats["query"] == want.stats["query"]


def test_fleet_mode_scan_matches_host():
    from repro.retrieval import RetrievalConfig, Retriever
    rng = np.random.default_rng(23)
    motifs = rng.integers(0, 10, size=(6, 8))
    data = motifs[rng.integers(0, 6, 60)]
    m = rng.random((60, 8)) < 0.2
    data = np.where(m, rng.integers(0, 10, size=(60, 8)), data)
    r = Retriever.build(
        RetrievalConfig("levenshtein", execution="fleet", workers=2,
                        kernel_backend="pallas", kernel_exec="scan",
                        tight_bounds=True), data)
    queries = [data[i][:ln] for i, ln in zip((1, 7, 22), (7, 8, 6))]
    want = r.batch(queries).via("host").range(2.0)
    got = r.batch(queries).range(2.0)
    assert got.hits == want.hits, "fleet scan-backend hit drift"
