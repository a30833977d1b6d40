"""Native C++ host components: differential tests against the numpy
reference implementations (cell-list neighbor builder replacing vesin,
sample store replacing DDStore/Adios-shmem — SURVEY.md §2.8).
"""

import os

import numpy as np
import pytest

from hydragnn_tpu.native import available

pytestmark = pytest.mark.skipif(
    not available(), reason="native library could not be built"
)


def _canon(ei, sh=None):
    keys = (ei[1], ei[0]) if sh is None else (
        sh[:, 2], sh[:, 1], sh[:, 0], ei[1], ei[0]
    )
    idx = np.lexsort(keys)
    return ei[:, idx], (None if sh is None else sh[idx])


def test_radius_graph_matches_numpy():
    from hydragnn_tpu.native import radius_graph_native
    from hydragnn_tpu.ops.neighbors import _cell_list_pairs

    rng = np.random.default_rng(3)
    for n in (1, 2, 17, 300):
        pos = rng.uniform(0, 5.0, (n, 3))
        ei_n, _ = _canon(radius_graph_native(pos, 1.4))
        s, r, _ = _cell_list_pairs(pos, 1.4, loop=False)
        ei_p, _ = _canon(np.stack([s, r]).astype(np.int64))
        assert np.array_equal(ei_n, ei_p), n


def test_radius_graph_pbc_matches_numpy():
    from hydragnn_tpu.native import radius_graph_pbc_native

    os.environ["HYDRAGNN_TPU_NO_NATIVE"] = "1"
    try:
        from hydragnn_tpu.ops.neighbors import radius_graph_pbc

        rng = np.random.default_rng(5)
        cell = np.array([[5.0, 0, 0], [0.7, 4.5, 0], [0.1, 0.4, 5.5]])
        for pbc in [(True, True, True), (True, False, True), (False,) * 3]:
            pos = rng.uniform(-3, 8.0, (40, 3))
            ein, shn = radius_graph_pbc_native(pos, cell, 1.6, pbc)
            eip, shp = radius_graph_pbc(pos, cell, 1.6, pbc=pbc)
            ein, shn = _canon(ein, shn)
            eip, shp = _canon(eip, shp)
            assert np.array_equal(ein, eip), pbc
            np.testing.assert_allclose(shn, shp, atol=1e-9)
    finally:
        os.environ.pop("HYDRAGNN_TPU_NO_NATIVE", None)


def test_dispatch_through_public_api():
    """ops.neighbors.radius_graph must give identical results with the
    native path on and off (including max_neighbours capping)."""
    from hydragnn_tpu.ops import neighbors

    rng = np.random.default_rng(7)
    pos = rng.uniform(0, 4.0, (80, 3))
    ei_native = neighbors.radius_graph(pos, 1.5, max_neighbours=6)
    os.environ["HYDRAGNN_TPU_NO_NATIVE"] = "1"
    try:
        ei_numpy = neighbors.radius_graph(pos, 1.5, max_neighbours=6)
    finally:
        os.environ.pop("HYDRAGNN_TPU_NO_NATIVE", None)
    a, _ = _canon(ei_native)
    b, _ = _canon(ei_numpy)
    assert np.array_equal(a, b)


def test_sample_store_roundtrip():
    from hydragnn_tpu.native import SampleStore

    recs = [os.urandom(int(k)) for k in (1, 100, 0, 4096)]
    st = SampleStore([len(r) for r in recs])
    for i, r in enumerate(recs):
        st.put(i, r)
    assert len(st) == len(recs)
    for i, r in enumerate(recs):
        assert st.get(i) == r
    with pytest.raises(IndexError):
        st.get(99)
    st.close()


def test_store_dataset_roundtrip():
    from hydragnn_tpu.data.diststore import (
        StoreDataset,
        pack_sample,
        shard_for_process,
        unpack_sample,
    )
    from hydragnn_tpu.data.graph import GraphSample

    rng = np.random.default_rng(0)
    samples = []
    for i in range(5):
        n = int(rng.integers(3, 7))
        samples.append(
            GraphSample(
                x=rng.normal(size=(n, 2)).astype(np.float32),
                pos=rng.normal(size=(n, 3)).astype(np.float32),
                edge_index=np.stack(
                    [np.arange(n - 1), np.arange(1, n)]
                ).astype(np.int64),
                y_graph=np.array([float(i)], np.float32),
                energy=-float(i),
                dataset_id=i % 2,
            )
        )
    # pack/unpack identity
    s2 = unpack_sample(pack_sample(samples[0]))
    np.testing.assert_array_equal(s2.x, samples[0].x)
    assert s2.energy == samples[0].energy
    assert s2.edge_attr is None
    # store-backed dataset
    ds = StoreDataset.build(samples)
    assert len(ds) == 5
    for i in range(5):
        np.testing.assert_array_equal(ds[i].pos, samples[i].pos)
        assert ds[i].dataset_id == samples[i].dataset_id
    ds.close()
    # host shard partition covers everything exactly once
    parts = [list(shard_for_process(11, p, 4)) for p in range(4)]
    assert sorted(sum(parts, [])) == list(range(11))


def test_sample_store_shared_memory():
    from hydragnn_tpu.native import SampleStore

    name = f"/hgtpu_pytest_{os.getpid()}"
    st = SampleStore([8, 8], shm_name=name)
    st.put(0, b"abcdefgh")
    st.put(1, b"01234567")
    reader = SampleStore.attach(name)
    assert reader.get(0) == b"abcdefgh"
    assert reader.get(1) == b"01234567"
    reader.close()
    st.close()
    # after the owner closes, the shm name must be gone
    with pytest.raises(RuntimeError):
        SampleStore.attach(name)


def test_build_is_keyed_on_source_hash(monkeypatch, tmp_path):
    """A binary built from other sources is never loaded: the library's
    name carries a hash of the two .cpp files, an old-style or stale
    ``libhgtpu_native*.so`` next to them is ignored and swept, and an
    edited source builds under a new name."""
    import shutil

    from hydragnn_tpu.native import bindings

    for name in ("celllist.cpp", "samplestore.cpp"):
        shutil.copy(os.path.join(bindings._HERE, name), tmp_path / name)
    stale = tmp_path / "libhgtpu_native.so"
    stale.write_bytes(b"not a shared object, and newer than the sources")
    monkeypatch.setattr(bindings, "_HERE", str(tmp_path))

    assert bindings._build() is not None
    built = sorted(p.name for p in tmp_path.glob("libhgtpu_native*.so"))
    assert len(built) == 1 and built[0] != stale.name

    with open(tmp_path / "celllist.cpp", "a") as f:
        f.write("\n// edited\n")
    assert bindings._build() is not None
    rebuilt = sorted(p.name for p in tmp_path.glob("libhgtpu_native*.so"))
    assert len(rebuilt) == 1 and rebuilt != built
