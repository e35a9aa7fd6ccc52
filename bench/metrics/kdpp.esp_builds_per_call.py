"""ESP tables the k-DPP sampler builds per sampling call: the program's
``dpp.kdpp.esp_builds`` counter over the window's calls. Nothing when the
window made no call, the program opens no ``dpp.sample`` span or has no
such counter (a program without this instrumentation reads nothing,
not 0)."""


def read(r):
    calls = r.work.get("calls", 0)
    if (not calls or "dpp.kdpp.esp_builds" not in r.counters
            or not any(h[0] == "dpp.sample" for h in r.trace.host)):
        return None
    return r.counters["dpp.kdpp.esp_builds"] / calls
