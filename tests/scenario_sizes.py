"""The scenario library at test size, for the library-wide sweeps.

`serving_production_stream` is shrunk to 5,000 requests: what those sweeps
check is event order and decision equality, which do not depend on the
stream's length, and the suite should pay seconds there, not minutes.
"""
import dataclasses

SHRINK = {"serving_production_stream": 5_000}


def sized(spec):
    n = SHRINK.get(spec.name)
    if n is not None:
        spec = dataclasses.replace(
            spec, workload=dataclasses.replace(spec.workload, stream_requests=n))
    return spec
