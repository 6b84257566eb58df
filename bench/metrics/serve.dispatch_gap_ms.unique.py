"""Mean milliseconds on the device timeline from the end of one wave's
``jit_client_stage`` to the start of the next wave's ``jit_server_stage``,
both inside the traced window's read part."""
from bench import stages


def read(rec):
    return stages.dispatch_gap_ms(rec.get("stages"))
