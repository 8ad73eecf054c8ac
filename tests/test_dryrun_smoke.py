"""Dry-run machinery smoke test (subprocess: needs forced host devices).

The full 512-device sweep lives in artifacts/ (launch/dryrun.py); this test
proves the lowering path end-to-end on a small forced mesh so CI catches
sharding regressions quickly.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, devices=16, timeout=540):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout[-2000:] + "\n" + out.stderr[-4000:]
    return out.stdout


def test_reduced_cells_lower_on_4x4_mesh():
    stdout = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config, reduced, TrainConfig
        from repro.models import Model
        from repro.sharding import rules as rules_lib
        from repro.train import step as step_lib

        mesh = jax.make_mesh((4, 4), ("data", "model"))
        for arch in ["qwen3-0.6b", "granite-moe-1b-a400m", "xlstm-1.3b",
                     "hymba-1.5b", "whisper-small"]:
            cfg = reduced(get_config(arch)).replace(
                d_model=64, n_heads=4, n_kv=2, d_ff=128)
            model = Model(cfg)
            tcfg = TrainConfig()
            state_abs = step_lib.abstract_state(model, tcfg)
            state_sh = step_lib.state_shardings(model, tcfg, mesh)
            specs = {
                "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                "targets": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                "mask": jax.ShapeDtypeStruct((8, 64), jnp.float32),
            }
            if cfg.frontend == "audio":
                specs["enc_embeds"] = jax.ShapeDtypeStruct(
                    (8, cfg.encoder_len, cfg.d_model), jnp.float32)
            if cfg.frontend == "vision":
                specs["prefix_embeds"] = jax.ShapeDtypeStruct(
                    (8, cfg.frontend_len, cfg.d_model), jnp.float32)
            bsh = rules_lib.batch_shardings_for(specs, mesh)
            fn = step_lib.build_train_step(model, tcfg)
            lowered = jax.jit(fn, in_shardings=(state_sh, bsh),
                              out_shardings=(state_sh, None)).lower(
                                  state_abs, specs)
            compiled = lowered.compile()
            cost = compiled.cost_analysis()
            assert cost.get("flops", 0) > 0, arch
            print("LOWERED", arch)
        print("DRYRUN-SMOKE-OK")
    """)
    assert "DRYRUN-SMOKE-OK" in stdout


def test_production_mesh_shapes():
    stdout = _run("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        assert m1.devices.shape == (16, 16)
        assert m1.axis_names == ("data", "model")
        m2 = make_production_mesh(multi_pod=True)
        assert m2.devices.shape == (2, 16, 16)
        assert m2.axis_names == ("pod", "data", "model")
        print("MESH-OK")
    """, devices=512)
    assert "MESH-OK" in stdout


def test_artifacts_exist_and_wellformed():
    """The committed sweep must cover all 40 cells x 2 meshes."""
    adir = os.path.join(REPO, "artifacts", "dryrun")
    if not os.path.isdir(adir):
        pytest.skip("no artifacts directory (sweep not run)")
    import glob
    base = [p for p in glob.glob(os.path.join(adir, "*.json"))
            if "__opt" not in p and "__g1" not in p and "__r" not in
            os.path.basename(p).split("__")[-1]]
    cells = {}
    for p in base:
        with open(p) as f:
            cells[os.path.basename(p)] = json.load(f)
    meshes = {"16x16", "2x16x16"}
    seen = {m: 0 for m in meshes}
    for name, c in cells.items():
        mesh = name[:-5].split("__")[2]
        if mesh in meshes:
            seen[mesh] += 1
            assert c["status"] in ("ok", "skipped"), (name, c["status"])
    for m, n in seen.items():
        assert n == 40, (m, n)
