"""Device time per answer in the programs that build the round timelines:
the round engine (``vecsim_*``) for failure-free rounds, the crash splice
(``one_schedule``) under crash schedules."""


def read(reduced):
    if reduced is None or not reduced.answers:
        return None
    t = reduced.module_seconds(lambda name: name.startswith("jit_vecsim_")
                               or name == "jit_one_schedule")
    return 1e3 * t / reduced.answers if t > 0 else None
