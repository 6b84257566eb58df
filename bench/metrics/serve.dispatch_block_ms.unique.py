"""Mean milliseconds of the serve runtime's ``serve/account`` annotations
inside the traced window's read part: how long dispatching a wave holds the
host on the device."""
from bench import stages


def read(rec):
    return stages.span_ms(rec.get("stages"), "serve/account")
