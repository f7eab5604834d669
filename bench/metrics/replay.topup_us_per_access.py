"""Device op self time under the scope ``ibex.topup`` (phase 0 of the replay
window step, the background demotion top-up) per access replayed in the
traced window, in microseconds; an op takes its scope from the HLO metadata
of the replay program (``hlo_scopes``). Null where the program declares no
scopes."""
import hlo_scopes


def read(facts, red, peaks):
    scope_s = hlo_scopes.scope_seconds(red)
    if not scope_s or not facts["accesses"]:
        return None
    return scope_s.get("ibex.topup", 0.0) / facts["accesses"] * 1e6
