"""Mean queue wait plus linger per request in the window, in ms, from
the serve stage histograms `serve.stage.{queue_wait,linger}_s`."""


def read(run):
    if not run.stages:
        return None
    count = run.stages["serve.stage.queue_wait_s"][0]
    if not count:
        return None
    total = sum(t for _, t in run.stages.values())
    return 1e3 * total / count
