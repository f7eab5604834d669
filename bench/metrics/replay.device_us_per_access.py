"""Device time of the replay programs (``_replay_windows``, and
``_replay_serial`` for a padded tail) per access replayed in the traced
window, in microseconds."""


def read(facts, red, peaks):
    t = red.modules_matching("_replay_windows") + \
        red.modules_matching("_replay_serial")
    if t <= 0 or not facts["accesses"]:
        return None
    return t / facts["accesses"] * 1e6
