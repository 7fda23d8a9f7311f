"""Mini dry-run: 8 fake host devices in a subprocess (XLA flags must be
set before jax initializes, so these run out-of-process), reduced configs,
(2,4) mesh — proves the lower+compile+analyse path end-to-end without the
cost of the full 256/512-chip sweep (which artifacts/dryrun holds)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.dist import sharding as shd
    from repro.launch import steps as steps_mod, hlo as hlo_mod
    from repro.launch.mesh import make_mesh
    from repro.launch.shapes import batch_specs, decode_specs
    from repro.models.model import build_model
    from repro.optim import OptimizerConfig

    arch, kind = sys.argv[1], sys.argv[2]
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    mesh = make_mesh((2, 4), ("data", "model"))
    hp = steps_mod.TrainHParams(
        optimizer=OptimizerConfig(), microbatches=2)
    with shd.use_mesh(mesh):
        if kind == "train":
            step = steps_mod.make_train_step(model, hp)
            state_abs = steps_mod.abstract_train_state(model, hp)
            state_sh = steps_mod.train_state_shardings(mesh, model, hp)
            specs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
            if cfg.frontend == "audio":
                specs["audio_frames"] = jax.ShapeDtypeStruct(
                    (8, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
            if cfg.frontend == "vision":
                specs["vision_embeds"] = jax.ShapeDtypeStruct(
                    (8, 8, cfg.d_model), jnp.bfloat16)
                specs["vision_positions"] = jax.ShapeDtypeStruct(
                    (3, 8, 8), jnp.int32)
            bsh = steps_mod.batch_shardings(mesh, specs)
            lowered = jax.jit(step, in_shardings=(state_sh, bsh),
                              donate_argnums=(0,)).lower(state_abs, specs)
        else:
            dstep = steps_mod.make_decode_step(model)
            params_abs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16)
                if jnp.issubdtype(a.dtype, jnp.floating) else a,
                model.abstract())
            psh = shd.param_shardings(mesh, params_abs, model.axes())
            from repro.models import serve as serve_mod
            cache = jax.eval_shape(
                lambda: serve_mod.init_cache(cfg, 8, 64))
            csh = steps_mod.cache_shardings(mesh, cache)
            lowered = jax.jit(
                dstep,
                in_shardings=(psh, shd.batch_sharding(mesh, (8,)),
                              csh, NamedSharding(mesh, P())),
                donate_argnums=(2,)).lower(
                params_abs, jax.ShapeDtypeStruct((8,), jnp.int32), cache,
                jax.ShapeDtypeStruct((), jnp.int32))
        compiled = lowered.compile()
        ha = hlo_mod.analyse_hlo(compiled.as_text())
        ma = compiled.memory_analysis()
        print(json.dumps({
            "flops": ha["flops"], "bytes": ha["bytes"],
            "collectives": ha["collectives"]["total"],
            "temp": ma.temp_size_in_bytes}))
""")


def _run(arch, kind):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, arch, kind],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "rwkv6-3b", "grok-1-314b",
                                  "recurrentgemma-9b", "whisper-base"])
def test_mini_dryrun_train(arch):
    r = _run(arch, "train")
    assert r["flops"] > 0
    assert r["collectives"] > 0          # the mesh is actually used


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "rwkv6-3b"])
def test_mini_dryrun_decode(arch):
    r = _run(arch, "decode")
    assert r["flops"] > 0
