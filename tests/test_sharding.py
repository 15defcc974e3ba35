"""Logical-axis rules: resolution, divisibility fallback, overrides."""
import jax
from jax.sharding import PartitionSpec as P

from jax.sharding import AbstractMesh

from repro import sharding as Sh


def _mesh():
    # production-shaped abstract mesh: rule logic needs names+sizes only
    return AbstractMesh((16, 16), ("data", "model"))


def test_basic_resolution():
    m = _mesh()
    assert Sh.resolve_spec(("batch", None, "mlp"), m) == P("data", None,
                                                           "model")
    assert Sh.resolve_spec(("vocab", "embed_p"), m) == P("model", "data")


def test_divisibility_fallback():
    m = _mesh()
    # kv_heads=8 cannot shard over model=16 -> dropped
    spec = Sh.resolve_spec(("batch", None, "kv_heads", None), m,
                           (256, 4, 8, 16))
    assert spec == P("data")
    # but kv_heads=32 shards fine
    spec = Sh.resolve_spec(("batch", None, "kv_heads", None), m,
                           (256, 4, 32, 16))
    assert spec == P("data", None, "model")


def test_missing_axis_dropped():
    m = _mesh()  # no "pod" axis
    spec = Sh.resolve_spec(("batch",), m, (256,))
    assert spec == P("data")   # ("pod","data") -> data only


def test_multipod_batch_axes():
    m = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    spec = Sh.resolve_spec(("batch", None), m, (256, 4096))
    assert spec == P(("pod", "data"))


def test_no_double_axis_use():
    m = _mesh()
    spec = Sh.resolve_spec(("mlp", "heads"), m, (64, 64))
    # both want "model"; only the first gets it
    assert spec == P("model")


def test_rules_override_context():
    m = _mesh()
    with Sh.rules({"mlp": "data"}):
        assert Sh.resolve_spec((None, "mlp"), m, (4, 64)) == P(None, "data")
    assert Sh.resolve_spec((None, "mlp"), m, (4, 64)) == P(None, "model")


def test_trailing_nones_trimmed():
    m = _mesh()
    spec = Sh.resolve_spec(("batch", None, None), m, (256, 2, 2))
    assert spec == P("data")


def test_cache_seq_prioritized_over_kv_heads():
    """Decode cache (B, T, Hkv, Dh): T takes the model axis; an
    indivisible Hkv falls back to replicated."""
    m = _mesh()
    spec = Sh.resolve_spec(("batch", "cache_seq", None, None), m,
                           (128, 32768, 8, 128))
    assert spec == P("data", "model")
