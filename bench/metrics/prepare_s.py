"""Host seconds of ``PMVEngine.prepare(spec)``: partition, plan, pack,
device_put and the step's jit wrapper (layer: prepare)."""


def read(r):
    return r.prepare_s
