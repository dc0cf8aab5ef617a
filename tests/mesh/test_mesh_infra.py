"""Mesh serving infrastructure: negative controls, the continuous
batcher under a mesh, and host-mesh construction/validation."""


def test_misreplicated_table_slab_is_caught(mesh_run):
    """A table slab claiming replicated sharding with corrupted buffers
    off device 0 must fail the sharded-vs-reference assertion — the
    harness's reason for comparing against the unsharded program rather
    than the two sharded backends against each other."""
    out = mesh_run("misreplicated")
    assert "diverges from the single-device reference" in out["caught"]


def test_continuous_batcher_under_mesh(mesh_run):
    """ContinuousBatcher(mesh=2x2) drains the same request mix to the
    same per-request outputs as the single-device batcher (admission,
    prefill replay, eviction churn included)."""
    out = mesh_run("batcher")
    assert len(out["outputs"]) == 6


def test_host_mesh_validation(mesh_run):
    """make_host_mesh rejects oversubscribed / degenerate shapes with an
    actionable error and builds GSPMD-auto axes."""
    out = mesh_run("mesh_helpers")
    assert out["devices"] == 8
