"""Device milliseconds per sweep call spent outside the fused kernel's
program: the XLA random draws (sites, batch sizes, uniforms, Gumbel noise)
and key splits the sweep builder runs before the kernel.  The kernel's
program is the jitted ``jit_<engine>_sweep``; every other program the
window ran belongs to the draws."""


def read(rec, red):
    kernel = f"jit_{rec['engine'].replace('-', '_')}_sweep"
    if kernel not in red["modules"] or not rec["calls"]:
        return None
    s = sum(sec for m, sec in red["modules"].items() if m != kernel)
    return 1e3 * s / rec["calls"]
