"""Device milliseconds a step of the client stage: the time of the
``jit_client_stage`` runs wholly inside the traced window's read part, over
the padded steps those waves scanned (the runtime's ``client_scan_steps``
histogram, matched to the runs in dispatch order)."""
from bench import stages


def read(rec):
    return stages.step_ms(rec.get("stages"), stages.CLIENT,
                          "client_scan_steps")
