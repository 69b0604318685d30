"""Engine host loop: device-idle time under the host phases of
``ServeEngine.step`` (its ``serve.admit``, ``serve.schedule``,
``serve.upload``, ``serve.launch``, ``serve.account`` and ``serve.emit``
spans; not ``serve.fetch``, where the host waits on the device), ms per
step of the traced window."""
import phases


def read(run):
    r = phases.of_run(run)
    if not r or not r["steps"]:
        return None
    idle = r["idle_by_phase"]
    return 1e3 * sum(idle.get(n, 0.0) for n in phases.HOST_PHASES) \
        / r["steps"]
