"""Demonstrates the full parallelism menu on a virtual device mesh:
data (dp), sequence (sp via ring attention), tensor (tp), the expert layer
an ``ep`` axis wraps (one device's held experts), and pipeline (pp via the
GPipe schedule).

These are the new-framework extensions beyond the 2017 reference
(SURVEY.md §2.3 last row); run on a real pod the same code spans chips
over ICI.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python train_moe_pipeline.py
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np  # noqa: E402


def main():
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    import jax.numpy as jnp
    from mxnet_tpu import parallel

    rs = np.random.RandomState(0)
    E, F = 16, 32

    # --- the expert layer: top-2 of 4 sigmoid-routed gated experts, of
    # which this device holds experts 1 and 2 (its share under an ``ep``
    # axis of two devices); dropless, no capacity
    n_exp = 4
    x = rs.randn(n_exp, 8, E).astype(np.float32)
    out, counts = parallel.moe_layer(
        jnp.asarray(x),
        jnp.asarray(rs.randn(n_exp, E).astype(np.float32)),
        jnp.zeros((n_exp,), jnp.float32),
        jnp.asarray(rs.randn(2, E, F).astype(np.float32) * 0.1),
        jnp.asarray(rs.randn(2, E, F).astype(np.float32) * 0.1),
        jnp.asarray(rs.randn(2, F, E).astype(np.float32) * 0.1),
        top_k=2, experts_held=(1, 2))
    print("positions per expert", np.asarray(counts))
    print("moe_layer out", out.shape)

    # --- pipeline parallelism: 4 stages, 6 microbatches -------------------
    n_pp = 4
    mesh = parallel.make_mesh({"pp": n_pp})
    w = rs.randn(n_pp, E, E).astype(np.float32) * 0.3
    b = rs.randn(n_pp, E).astype(np.float32) * 0.1
    mb = rs.randn(6, 4, E).astype(np.float32)

    def stage(p, t):
        return jnp.tanh(t @ p["w"] + p["b"])

    out = parallel.pipeline_apply(stage, {"w": jnp.asarray(w),
                                          "b": jnp.asarray(b)},
                                  jnp.asarray(mb), mesh)
    print("pipeline out", out.shape)

    # --- dp x sp x tp: ring attention inside an SPMD train step -----------
    mesh = parallel.make_mesh({"dp": 2, "sp": 2, "tp": 2})
    B, H, S, D = 4, 2, 16, 8
    q = jnp.asarray(rs.randn(B, H, S, D).astype(np.float32))
    out = parallel.ring_attention(q, q, q, mesh, axis_name="sp",
                                  batch_axis_name="dp", causal=True)
    print("ring attention out", out.shape)
    print("OK")


if __name__ == "__main__":
    main()
