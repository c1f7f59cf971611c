"""Seconds of the imports on the way to the engine — the package's root import
(jax's own where nothing imported it before) and the lazy ``runtime.engine``
import inside ``deepspeed_tpu.initialize`` — as the program stamped them:
``import_s`` of the ``ds.setup`` event (benchmark/setup_record.py)."""

from benchmark import setup_record


def read(run):
    return setup_record.value(run, "import_s")
