"""Lanes of the slab fold's three staged table probes (``resp.lookup``,
``conn.upsert``, the edge table's in ``dep.fold``) that stage 1 left open,
per 1,000 conn + resp events folded: the movement of the device counter
``engine_probe_residue_lanes`` over that of ``engine_conn_folded`` +
``engine_resp_folded``, all three of one health readback a tick
(engine/step.py:engine_health_vec). Nothing to read on a program without
the staged probe. Layer: fold, device."""


def read(ctx):
    lanes = ctx.counter("engine_probe_residue_lanes")
    if lanes is None:
        return None
    events = (ctx.counter("engine_conn_folded") or 0) \
        + (ctx.counter("engine_resp_folded") or 0)
    return 1e3 * lanes / events if events > 0 else None
