"""The serving engine's own host-side readings over the window
(``Engine.metrics`` and the step histogram), as the driver copied them:
``engine.decode_step_ms_mean`` the mean decode dispatch (ends in a
device_get; ``DecodeMetrics.decode_s / decode_steps``), ``engine.slot_occupancy`` live slots / slots averaged over decode
steps, ``engine.compiles_in_window`` decode + prefill signatures first built
inside the window (should read 0)."""


def read(name, ctx):
    eng = ctx["observed"].get("engine")
    if not eng:
        return None
    value = eng.get(name.split(".", 1)[1])
    return None if value is None else float(value)
