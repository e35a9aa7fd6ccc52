"""Device-to-host syncs the facade makes per sampling call: the program's
``dpp.host_syncs`` counter over the window's calls. Nothing when the
window made no call or the program opens no ``dpp.sample`` span (a
program without this instrumentation has no such counter either)."""


def read(r):
    calls = r.work.get("calls", 0)
    if not calls or not any(h[0] == "dpp.sample" for h in r.trace.host):
        return None
    return r.counters.get("dpp.host_syncs", 0) / calls
