"""Index build (``core/build_engine.py``): host-clock time of one wave.

The ``repro.build.waves`` part of the program's build record
(``repro.core.telemetry`` ``latest_build``: the wave scan, ended by a
``block_until_ready``, so it holds the device's work) over the number of
waves, in ms.  Compilation of the scan falls in it on a cold cache.
Nothing is read where the program keeps no build record, or where the
build ran no waves.
"""


def read(run):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    latest_build = getattr(telemetry, "latest_build", None)
    rec = latest_build() if latest_build is not None else None
    if rec is None or not rec.waves:
        return None
    return 1e3 * rec.seconds["waves"] / rec.waves
