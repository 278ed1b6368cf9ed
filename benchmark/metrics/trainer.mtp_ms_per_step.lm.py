"""Device milliseconds a step in the multi-token module's three programs
(`jit_mtp_forward`, `jit_mtp_head`, `jit_mtp_backward`), whatever the
scope: its projection and stream ends (`mv.lm.mtp`), its layer's parts
under their own scopes, its pass of the head (`mv.lm.mtp.head`); busiest
chip, traced window. 0 where the driver says this rank holds no module
(`obs.shapes["modules"]` 0: `xing29b.ps-4k`, whose module lies on a
further rank, PERF.md section 4) and no such program ran; None where the
driver says nothing of modules (another model, an older program)."""

STEMS = ("jit_mtp_forward", "jit_mtp_head", "jit_mtp_backward")


def read(obs):
    if obs.trace is None or not obs.traced or not obs.traced.rounds \
            or "modules" not in obs.shapes:
        return None
    found = [sum(obs.trace["scopes"][stem].values()) for stem in STEMS
             if stem in obs.trace.get("scopes", {})]
    if not found and obs.shapes["modules"]:
        return None
    return sum(found) * 1e3 / obs.traced.rounds
