"""Pipeline parallelism: pipelined == sequential, grads flow (subprocess
with a 4-stage device mesh)."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_pipeline_matches_sequential_and_grads():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import pipeline_apply, stack_layer_groups

        L, d, B, S_stages, M = 8, 16, 8, 4, 4
        key = jax.random.PRNGKey(0)
        W = jax.random.normal(key, (L, d, d)) * 0.3
        x = jax.random.normal(jax.random.fold_in(key, 1), (B, d))

        def seq(W, x):
            for i in range(L):
                x = jnp.tanh(x @ W[i])
            return x

        def stage_fn(w_group, x):           # (L/S, d, d)
            def body(x, w):
                return jnp.tanh(x @ w), None
            return jax.lax.scan(body, x, w_group)[0]

        mesh = jax.make_mesh((S_stages,), ("stage",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        Wst = stack_layer_groups(W, S_stages)
        y_pipe = pipeline_apply(stage_fn, Wst, x, mesh=mesh,
                                axis="stage", n_micro=M)
        y_seq = seq(W, x)
        err = float(jnp.max(jnp.abs(y_pipe - y_seq)))
        assert err < 1e-5, err
        print("PIPE FWD OK", err)

        # gradient through the pipeline (autodiff through ppermute)
        def loss_pipe(Wst):
            return jnp.sum(pipeline_apply(stage_fn, Wst, x, mesh=mesh,
                                          axis="stage", n_micro=M) ** 2)
        def loss_seq(W):
            return jnp.sum(seq(W, x) ** 2)
        g_pipe = jax.grad(loss_pipe)(Wst).reshape(W.shape)
        g_seq = jax.grad(loss_seq)(W)
        gerr = float(jnp.max(jnp.abs(g_pipe - g_seq)))
        assert gerr < 1e-4, gerr
        print("PIPE GRAD OK", gerr)
    """)
    assert "PIPE FWD OK" in out and "PIPE GRAD OK" in out


def test_pipeline_bubble_accounting():
    """GPipe bubble fraction = (S-1)/(M+S-1): more microbatches -> smaller."""
    S = 4
    for M, expect in ((1, 3 / 4), (4, 3 / 7), (12, 3 / 15)):
        bubble = (S - 1) / (M + S - 1)
        assert abs(bubble - expect) < 1e-9
