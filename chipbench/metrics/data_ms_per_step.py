"""Host milliseconds a step waits for its batch: `TokenPipeline.batch_at`
through fsio, the labels and the host-to-device copy, by the host clock
around the harness's batch fetch, in the traced part."""


def read(run):
    p = run.parts.get("trace")
    if p is None or not p.ops:
        return None
    return 1e3 * p.amount("data_s") / len(p.ops)
