"""Share of the model calls the sampling engine ran in the window that were
padding (masked rows and steps of the shape tiers), from the serve
runtime's counters."""


def read(rec):
    c = rec["counters"]
    physical = c["server_calls_physical"] + c["client_calls_physical"]
    return 100.0 * c["padded_model_calls"] / physical if physical else None
