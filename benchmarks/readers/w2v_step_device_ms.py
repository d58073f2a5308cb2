"""``w2v_step_device_ms``: device time of the fused
``train_device_steps`` program (the traffic file's ``step_program``, as
the ``XLA Modules`` line names it) over the steps it ran, averaged over
the chips."""


def read(ctx):
    t = ctx.tracered
    prog = (t or {}).get("programs", {}).get(ctx.traffic["step_program"])
    if not prog or not prog["runs"]:
        return None
    runs = prog["runs"] / t["chips"]
    return 1e3 * (prog["s"] / t["chips"]) \
        / (runs * ctx.config["steps_per_dispatch"])
