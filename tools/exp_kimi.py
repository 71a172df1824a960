"""The two caches of ``reasoning-offline`` alone on the chip: the KDA
step's kernel against XLA's lowering of the same step (outputs and
states compared on the device first, then ms and GB/s at the cell's
geometry, the state donated as the engine donates it), the latent
decode at three context lengths (ms and the live bytes a second), and
the two chunk forms at a 2,048-token chunk.

    PYTHONPATH=/root/repo python tools/exp_kimi.py          # the chip
    PYTHONPATH=/root/repo python tools/exp_kimi.py --tiny   # CPU rehearsal
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from mlcomp_tpu.models.kda import delta_chunks
from mlcomp_tpu.models.latent_attention import latent_chunk_attention
from mlcomp_tpu.ops.pallas.kda import kda_step, state_bytes_moved
from mlcomp_tpu.ops.pallas.latent_attention import buffer_len, latent_decode

HI = jax.lax.Precision.HIGHEST


def xla_step(q, k, v, log_a, beta, live, state):
    del live
    state = jnp.exp(log_a)[..., None] * state
    u = beta[..., None] * (
        v - jnp.einsum("bncd,bnc->bnd", state, k, precision=HI))
    state = state + k[..., None] * u[..., None, :]
    return jnp.einsum("bncd,bnc->bnd", state, q, precision=HI), state


def ms_of(fn, *args, carry=None, n=20):
    """(mean ms of ``fn(*args)``, the last call's result); with
    ``carry`` the last argument is donated and threaded through the
    calls (a state updated in place)."""
    def call(c):
        return fn(*args) if carry is None else fn(*args, c)

    out = call(carry)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = call(out[1] if carry is not None else None)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3, out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    tiny = ap.parse_args().tiny
    rows, heads, dh = (6, 4, 16) if tiny else (112, 32, 128)
    length, width, dc, chunk = (200, 128, 32, 64) if tiny \
        else (9729, 640, 512, 2048)
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = jax.random.normal(ks[0], (rows, heads, dh)) * dh ** -0.5
    k = unit(jax.random.normal(ks[1], (rows, heads, dh)))
    v = jax.random.normal(ks[2], (rows, heads, dh))
    log_a = -0.1 * jax.random.uniform(ks[3], (rows, heads, dh))
    beta = jax.random.uniform(ks[4], (rows, heads))
    state = jax.random.normal(ks[5], (rows, heads, dh, dh))
    want_o, want_s = jax.jit(xla_step)(q, k, v, log_a, beta, None, state)
    moved = state_bytes_moved(rows, heads, dh, dh)
    for share in (1.0, 0.75):
        live = jnp.arange(rows) < round(share * rows)
        for name, fn in (("kda_step", kda_step), ("xla", xla_step)):
            step = jax.jit(fn, donate_argnums=(6,))
            o, s = step(q, k, v, log_a, beta, live, state + 0.0)
            err = float(jnp.abs(jnp.where(live[:, None, None],
                                          o - want_o, 0.0)).max())
            took, _ = ms_of(step, q, k, v, log_a, beta, live,
                            carry=state + 0.0)
            print(json.dumps({
                "step": name, "live_share": share, "out_err": err,
                "state_err": float(jnp.abs(jnp.where(
                    live[:, None, None, None], s - want_s, 0.0)).max()),
                "ms": took, "GB_per_s_all_rows": moved / took / 1e6,
            }), flush=True)
    del state, want_s

    buf = buffer_len(length)
    cache = (0.3 * jax.random.normal(ks[6], (rows, buf, width))).astype(
        jnp.bfloat16)
    q_lat = (0.05 * jax.random.normal(ks[7], (rows, heads, width))).astype(
        jnp.bfloat16)
    new = jnp.zeros((rows, width), jnp.bfloat16)
    rng = np.random.default_rng(0)
    decode = jax.jit(
        lambda q, n, a, b, c: latent_decode(q, n, c, a, b, dc=dc),
        donate_argnums=(4,))
    for mean in (length // 12, length // 3, length * 4 // 5):
        ctx = np.clip(rng.normal(mean, mean / 4, rows), 8,
                      length - 8).astype(np.int32)
        start = jnp.asarray(rng.integers(0, 8, rows).astype(np.int32))
        stop = jnp.minimum(start + jnp.asarray(ctx), length)
        took, (_, cache) = ms_of(decode, q_lat, new, start, stop,
                                 carry=cache)
        print(json.dumps({
            "latent_decode_mean_context": int(mean), "ms": took,
            "live_tokens": int(ctx.sum()),
            # a token's latent and shared key part, bfloat16
            "live_GB_per_s": float(ctx.sum()) * (dc + dc // 8) * 2
            / took / 1e6,
        }), flush=True)

    q4 = jax.random.normal(ks[0], (1, chunk, heads, dh)) * dh ** -0.5
    k4 = unit(jax.random.normal(ks[1], (1, chunk, heads, dh)))
    v4 = jax.random.normal(ks[2], (1, chunk, heads, dh))
    la4 = -0.05 * jax.random.uniform(ks[3], (1, chunk, heads, dh))
    b4 = jax.random.uniform(ks[4], (1, chunk, heads))
    s0 = jnp.zeros((1, heads, dh, dh))
    print(json.dumps({"kda_chunk_form_ms": ms_of(
        jax.jit(delta_chunks), q4, k4, v4, la4, b4, s0, n=5)[0]}), flush=True)
    qc = (0.05 * jax.random.normal(ks[7], (1, chunk, heads, width))).astype(
        jnp.bfloat16)
    valid = jnp.ones((1, buf), bool)
    for first in (0, (length - chunk) // 2, length - chunk - 1):
        attend = jax.jit(lambda q, lat, ok, first=first:
                         latent_chunk_attention(q, lat, first, ok, dc))
        print(json.dumps({
            "latent_chunk_form_first_slot": int(first),
            "ms": ms_of(attend, qc, cache[:1], valid, n=5)[0]}), flush=True)


if __name__ == "__main__":
    main()
