"""``lm_step_device_ms``: device time of the jitted train step (the
traffic file's ``step_program``) per run."""


def read(ctx):
    t = ctx.tracered
    prog = (t or {}).get("programs", {}).get(ctx.traffic["step_program"])
    if not prog or not prog["runs"]:
        return None
    return 1e3 * prog["s"] / prog["runs"]
