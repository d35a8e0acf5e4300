"""The benchmark's configuration files against the program's registry, in
abstract shapes only (no weights are made)."""
import jax
from jax.sharding import PartitionSpec

from benchlib import serve, spec


def test_qwen3_8b_tp4_cell_maps_onto_the_registered_model():
    cell = spec.load_cell("qwen3-8b-tp4.offline-conv-2k")
    assert cell.chips == 4 and cell.traffic["requests"] == 2048
    conf = cell.config
    assert conf["reduced"] == [] and conf["engine"]["tp"] == 4
    ref = spec.load_module(spec.BENCH / "configs" / conf["reference"])
    mc = serve.model_config(conf, ref)
    from repro.configs import get_config
    from repro.distributed import tp as tp_lib
    from repro.models import model
    # the published sizes are the registered entry's, eps and head included
    assert mc == get_config("qwen3-8b")
    assert (mc.n_layers, mc.d_model, mc.n_heads, mc.n_kv_heads, mc.d_ff,
            mc.vocab_size, mc.head_dim, mc.norm_eps, mc.tie_embeddings) == \
        (36, 4096, 32, 8, 12288, 151936, 128, 1e-6, False)
    tp_lib.validate_tp(mc, 4)

    shapes = jax.eval_shape(lambda: model.init(mc, jax.random.PRNGKey(0)))
    specs = tp_lib.param_pspecs_tp(mc)
    per_device = sum(
        a.size * a.dtype.itemsize // (4 if "model" in s else 1)
        for a, s in zip(jax.tree.leaves(shapes), jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, PartitionSpec))))
    # 6.95 B layer parameters split four ways, embedding and head whole
    assert 5.5 * 2**30 <= per_device <= 5.6 * 2**30, per_device / 2**30
