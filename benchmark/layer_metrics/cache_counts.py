"""What the KDA and the latent-attention layers counted over the run
(``stats()["engine"]["kda"]`` and ``["latent"]``, after less before)
beside the engine's own steps and emitted tokens; None where the
program counts no such thing (a parent without the layers, a model
without them)."""

KEYS = {"kda": ("state_rows", "state_bytes"),
        "latent": ("tokens_attended", "bytes_read")}
ENGINE_KEYS = ("steps", "emitted_tokens")


def delta(ctx):
    """{"kda": {...}, "latent": {...}, "steps": n, "emitted_tokens": n}
    as ``stats1 - stats0``, or None without a single-token step of both
    kinds to count."""
    after = (ctx.get("stats1") or {}).get("engine") or {}
    if not all(after.get(group) for group in KEYS):
        return None
    before = (ctx.get("stats0") or {}).get("engine") or {}
    out = {
        group: {k: float(after[group][k])
                - float((before.get(group) or {}).get(k, 0.0)) for k in keys}
        for group, keys in KEYS.items()
    }
    for k in ENGINE_KEYS:
        out[k] = float(after[k]) - float(before.get(k, 0))
    counted = out["kda"]["state_rows"] > 0 \
        and out["latent"]["tokens_attended"] > 0 and out["steps"] > 0
    return out if counted else None


def layers_of(ctx, kind: str):
    """(the architecture's dims, how many of its layers are ``kind``)."""
    from benchmark import cells

    arch = cells.architecture(ctx["cell"].config)
    dims = arch.dims_of(ctx["cell"].config)
    return dims, sum(1 for a in dims["attn"] if a == kind)


def kernel_time_share(ctx, kernel: str):
    """Device time of the ops named ``%<kernel>`` over the device's busy
    time in the traced slice, in percent; None without a trace or a
    call."""
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    events = tr.kernel_events(
        lambda op: op.split(" = ")[0].startswith("%" + kernel)
    )
    busy = tr.busy_s()
    if not events or busy <= 0:
        return None
    took = sum(e - s for _, s, e in events) / 1e9 / len(tr.devices)
    return 100.0 * took / busy
