"""Seconds of backend compilation during set-up (``jax.monitoring``)."""


def read(rec):
    return rec.compile_setup_s
