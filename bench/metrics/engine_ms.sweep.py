"""Device time per query in the round engine's programs (``vecsim_*``:
the NIC scan and the min-plus relaxation of G_U and G_R rounds)."""


def read(reduced):
    if reduced is None or not reduced.answers:
        return None
    t = reduced.module_seconds(lambda name: name.startswith("jit_vecsim_"))
    return 1e3 * t / reduced.answers if t > 0 else None
