"""Device op self time under the scope ``ibex.mcache`` (phase 2 of the replay
window step, the metadata-cache window and the fast accesses' accounting)
per access replayed in the traced window, in microseconds; an op takes its
scope from the HLO metadata of the replay program (``hlo_scopes``). Null
where the program declares no scopes."""
import hlo_scopes


def read(facts, red, peaks):
    scope_s = hlo_scopes.scope_seconds(red)
    if not scope_s or not facts["accesses"]:
        return None
    return scope_s.get("ibex.mcache", 0.0) / facts["accesses"] * 1e6
