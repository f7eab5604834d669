"""Device time of the decode-step program (launched from ``Engine.step``
itself) per engine step in the traced window, in milliseconds."""


def read(facts, red, peaks):
    t = red.phase_s.get("serve.decode", 0.0)
    if t <= 0 or not facts["steps"]:
        return None
    return t / facts["steps"] * 1e3
