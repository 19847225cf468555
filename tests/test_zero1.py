"""ZeRO-1 optimizer state: each leaf's moments keep its shape, dim 0
chunked over the zero axes (``train/steps.py``)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models.common import ShardCtx, make_ctx
from repro.models.model import build_model
from repro.train import steps as st
from repro.train.steps import TrainerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A leading axis that 4 does not divide, a scalar, a bf16 leaf whose
# leading axis 4 does not divide, and one that divides.
PADDING_WORKER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.models.common import ShardCtx
    from repro.optim.optimizers import INITS, UPDATES, OptConfig
    from repro.train import steps as st

    opt = OptConfig(lr=1e-2, weight_decay=0.1)
    tcfg = st.TrainerConfig(opt=opt)
    ctx = ShardCtx(tp=1, dp=4)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    rng = np.random.default_rng(0)
    shapes = {"a": ((6, 8), jnp.float32), "s": ((), jnp.float32),
              "b": ((5, 3), jnp.bfloat16), "w": ((8, 4), jnp.bfloat16)}

    def draw():
        return {k: jnp.asarray(rng.standard_normal(s), d)
                for k, (s, d) in shapes.items()}

    params = draw()
    specs = {k: P() for k in params}
    zst = st.init_opt_state(tcfg, params, ctx, specs)["leaves"]
    ospecs = st.opt_pspecs(tcfg, specs, ctx)["leaves"]
    full = {k: INITS["adamw"](p) for k, p in params.items()}

    def split(outs):
        return ({k: o[0] for k, o in outs.items()},
                {k: o[1] for k, o in outs.items()})

    def zero1_step(params, grads, state, step):
        r = jax.lax.axis_index("data")
        return split({k: st.zero1_update(opt, params[k], grads[k], state[k],
                                         step, r, 4, ("data",))
                      for k in params})

    zstep = jax.jit(jax.shard_map(
        zero1_step, mesh=mesh, in_specs=(specs, specs, ospecs, P()),
        out_specs=(specs, ospecs), check_vma=False))
    fstep = jax.jit(lambda ps, gs, ss, step: split(
        {k: UPDATES["adamw"](opt, ps[k], gs[k], ss[k], step) for k in ps}))

    zp, fp = params, params
    for i in range(3):
        grads = draw()
        step = jnp.int32(i)
        zp, zst = zstep(zp, grads, zst, step)
        fp, full = fstep(fp, grads, full, step)
    for k, (shape, dtype) in shapes.items():
        assert zp[k].shape == shape and zp[k].dtype == dtype, k
        np.testing.assert_array_equal(np.asarray(zp[k], np.float32),
                                      np.asarray(fp[k], np.float32), k)
        d0 = shape[0] if shape else 1
        for name, m in zst[k].items():
            m = np.asarray(m)
            assert m.shape[0] == 4 * -(-d0 // 4), (k, m.shape)
            np.testing.assert_array_equal(
                m[:d0].reshape(shape), np.asarray(full[k][name]), k)
            assert not m[d0:].any(), (k, name, m[d0:])
    print("ALL_OK")
""")


def test_padded_leading_axis_and_scalar_on_four_devices():
    """Leaves whose leading axis 4 does not divide, and a scalar, take
    the ZeRO-1 update on 4 virtual devices: parameters equal the plain
    update's bit for bit, the moments' real rows equal its moments, and
    the padded rows stay zero."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", PADDING_WORKER], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "ALL_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-4000:]


@pytest.mark.parametrize("world", [1, 4])
def test_moment_layout_full_width_qwen2(world):
    """Abstract shapes only: at world 1 every moment of full-width
    qwen2-0.5b has its leaf's shape; at world 4 it is
    ``(4 * c0, *shape[1:])``, placed by dim 0 over the data axis."""
    cfg = get_config("qwen2-0.5b")
    ctx = make_ctx(cfg, 1, world)
    shapes, specs = build_model(cfg, ctx).abstract()
    tcfg = TrainerConfig()
    state = st.abstract_opt_state(tcfg, shapes, ctx, specs)["leaves"]
    ospecs = st.opt_pspecs(tcfg, specs, ctx)["leaves"]
    flat_p = jax.tree.leaves(shapes)
    flat_s = jax.tree.structure(shapes).flatten_up_to(state)
    flat_o = jax.tree.structure(shapes).flatten_up_to(ospecs)
    assert len(flat_p) == 15
    for p, moments, mspecs in zip(flat_p, flat_s, flat_o):
        c0 = st.opt_chunk_size(p.shape[0], world)
        assert set(moments) == {"m", "v"}
        for k, m in moments.items():
            assert m.dtype == jnp.float32
            assert m.shape == (world * c0, *p.shape[1:])
            assert mspecs[k] == P(("data",))
            if world == 1:
                assert m.shape == p.shape
        # no qwen2-0.5b leading axis needs a pad at world 4
        assert world * c0 == p.shape[0]


def test_moments_built_from_the_local_shard():
    """tp=2, dp=4: a leaf sharded over ``model`` on dim 1 keeps its local
    trailing shape; one sharded on dim 0 chunks its local rows; a scalar
    counts as (1,).  ``init_opt_state`` and ``abstract_opt_state``
    agree."""
    ctx = ShardCtx(tp=2, dp=4)
    shapes = {"col": jax.ShapeDtypeStruct((6, 8), jnp.bfloat16),
              "row": jax.ShapeDtypeStruct((10, 3), jnp.bfloat16),
              "s": jax.ShapeDtypeStruct((), jnp.float32)}
    specs = {"col": P(None, "model"), "row": P("model", None), "s": P()}
    want = {"col": (8, 4), "row": (8, 3), "s": (4,)}
    tcfg = TrainerConfig()
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    real = st.init_opt_state(tcfg, params, ctx, specs)["leaves"]
    abstract = st.abstract_opt_state(tcfg, shapes, ctx, specs)["leaves"]
    for k, shape in want.items():
        assert st.moment_shape(
            st.local_param_shapes(shapes, specs, ctx)[k].shape, 4) == shape
        for name in ("m", "v"):
            assert real[k][name].shape == abstract[k][name].shape == shape
            assert real[k][name].dtype == abstract[k][name].dtype \
                == jnp.float32
