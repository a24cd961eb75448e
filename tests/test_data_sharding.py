"""Data pipeline determinism + sharding-rule unit tests."""
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_local_mesh
from repro.runtime import lm_sharding as shd


def test_pipeline_deterministic():
    cfg = get_config("qwen2-1.5b", smoke=True)
    p1 = SyntheticLM(cfg, 64, 8, seed=3)
    p2 = SyntheticLM(cfg, 64, 8, seed=3)
    b1 = p1.batch(17)
    b2 = p2.batch(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(p1.batch(18)["tokens"], b1["tokens"])


def test_pipeline_shards_partition_batch():
    cfg = get_config("qwen2-1.5b", smoke=True)
    full = SyntheticLM(cfg, 32, 8, seed=0, shard=0, num_shards=1)
    sh0 = SyntheticLM(cfg, 32, 8, seed=0, shard=0, num_shards=2)
    sh1 = SyntheticLM(cfg, 32, 8, seed=0, shard=1, num_shards=2)
    assert sh0.local_batch == 4 and sh1.local_batch == 4
    assert full.batch(0)["tokens"].shape == (8, 32)
    # shards differ from each other (independent streams)
    assert not np.array_equal(sh0.batch(0)["tokens"],
                              sh1.batch(0)["tokens"])


def test_pipeline_iterator_prefetch():
    cfg = get_config("qwen2-1.5b", smoke=True)
    pipe = SyntheticLM(cfg, 16, 4, seed=1)
    it = pipe.iterator(start_step=5, prefetch=2)
    first = next(it)
    np.testing.assert_array_equal(first["tokens"], pipe.batch(5)["tokens"])
    next(it)
    it.close()


def test_vlm_audio_batches_have_memory():
    for arch in ("llama-3.2-vision-90b", "seamless-m4t-large-v2"):
        cfg = get_config(arch, smoke=True)
        b = SyntheticLM(cfg, 32, 2, seed=0).batch(0)
        assert "memory" in b and b["memory"].ndim == 3


def test_spec_dedupes_mesh_axes():
    rules = {"expert": "model", "embed": "data", "ff": "model", None: None}
    spec = shd.spec_for(("expert", "embed", "ff"), rules)
    assert spec == P("model", "data", None)


def test_spec_dedupe_with_tuple_axes():
    rules = {"batch": ("pod", "data"), "kv_seq": "data", None: None}
    spec = shd.spec_for(("batch", "kv_seq"), rules)
    assert spec == P(("pod", "data"), None)


class _FakeMesh:
    def __init__(self, shape, names):
        self.shape = dict(zip(names, shape))
        self.axis_names = names


def test_make_rules_divisibility_fallbacks():
    mesh = _FakeMesh((16, 16), ("data", "model"))
    cfg = get_config("qwen2-1.5b")   # 12 heads, kv=2: neither divides 16
    rules = shd.make_rules(mesh, cfg, global_batch=256)
    assert rules["heads"] is None and rules["kv_heads"] is None
    assert rules["ff"] == "model" and rules["vocab"] == "model"
    cfg7 = get_config("qwen2-7b")    # 28 heads: not divisible either
    assert shd.make_rules(mesh, cfg7, global_batch=256)["heads"] is None
    glm = get_config("glm4-9b")      # 32 heads divisible
    assert shd.make_rules(mesh, glm, global_batch=256)["heads"] == "model"
    mam = get_config("mamba2-780m")  # vocab 50280 % 16 != 0
    assert shd.make_rules(mesh, mam, global_batch=256)["vocab"] is None


def test_make_rules_batch_fallback():
    mesh = _FakeMesh((2, 16, 16), ("pod", "data", "model"))
    cfg = get_config("glm4-9b")
    r = shd.make_rules(mesh, cfg, global_batch=256)
    assert r["batch"] == ("pod", "data")
    r1 = shd.make_rules(mesh, cfg, global_batch=1, seq_shard=True)
    assert r1["batch"] is None and r1["kv_seq"] == "data"
    r2 = shd.make_rules(mesh, cfg, global_batch=2)
    assert r2["batch"] == ("pod",)


def test_local_mesh_covers_devices():
    mesh = make_local_mesh()
    assert int(np.prod(list(mesh.devices.shape))) >= 1
