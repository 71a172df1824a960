"""Test harness: force an 8-device virtual CPU mesh before JAX loads.

Real multi-chip hardware is unavailable in CI; sharding/collective tests run
on XLA's host-platform virtual devices instead (same SPMD partitioner, same
collective lowering).
"""

import os

# Must be set before the first `import jax` anywhere in the test process.
# Hard override (not setdefault): tests are CPU-only by contract, whatever
# the ambient environment says, and the worker child processes tests
# spawn inherit it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402


@pytest.fixture()
def tmp_db(tmp_path):
    return str(tmp_path / "mlcomp.sqlite")


# compiled-program pool per engine config (the _fns idiom from
# tests/test_engine_fused_admit.py), shared by the engine test files:
# pipeline depth is HOST-side only, so e.g. the depth-1 and depth-2
# arms of an equality pair share the same jitted
# dispatch/prefill/insert programs — compile once per key, not once
# per engine.  Keys are per-file tuples; files must not collide.
ENGINE_FNS_POOL: dict = {}


def loop_write_kv(caches, new, cur):
    """The int8 cache's single-token write up to PR 28, kept as the
    reference the kernel's ``append`` is held to: K and V row by row
    through ``_row_cursor_dus`` (EVERY row, at its cursor), each scale
    cache rewritten by a masked select.  ``caches`` = (k8, ks, v8, vs),
    ``new`` = (kq, ks_new, vq, vs_new) as ``decode_attention`` takes
    them."""
    import jax
    import jax.numpy as jnp

    from mlcomp_tpu.models.transformer import _row_cursor_dus

    k8, ks, v8, vs = caches
    kq, ks_new, vq, vs_new = new
    b, h_kv, _, l_buf = ks.shape
    hit = (
        jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, l_buf), 3)
        == cur[:, None, None, None]
    )
    return (
        _row_cursor_dus(k8, kq[:, :, None, :], cur, 2),
        jnp.where(hit, ks_new.reshape(b, h_kv, 1, 1).astype(ks.dtype), ks),
        _row_cursor_dus(v8, vq[:, :, None, :], cur, 2),
        jnp.where(hit, vs_new.reshape(b, h_kv, 1, 1).astype(vs.dtype), vs),
    )


def pallas_calls(jaxpr, inside=()):
    """(enclosing primitives, kernel name) of every ``pallas_call`` in a
    jaxpr and the jaxprs its equations carry."""
    import jax

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((inside, str(eqn.params["name"])))
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += pallas_calls(sub, inside + (eqn.primitive.name,))
    return found


def share_engine_fns(eng, key):
    pool = ENGINE_FNS_POOL.setdefault(key, {})
    eng._fns.update(pool)
    eng._fns_pool = pool
    return eng


def close_pooled_engine(eng):
    """Harvest the engine's compiled programs back into its pool,
    then close — the update must precede close() so programs compiled
    by THIS engine survive for the next one."""
    if hasattr(eng, "_fns_pool"):
        eng._fns_pool.update(eng._fns)
    eng.close()


@pytest.fixture(autouse=True)
def _clear_process_mesh():
    """The installed mesh is a process-wide global (production installs
    it once per Trainer/service lifetime); tests that install one and
    don't clean up would silently flip OTHER tests onto mesh-gated
    paths (sharded kernel islands, fold_norms disabled, the chunk
    kernel's XLA fallback) — the round-5 full-suite run caught exactly
    that. Every test starts and ends mesh-free."""
    yield
    from mlcomp_tpu.parallel.mesh import set_current_mesh

    set_current_mesh(None)
