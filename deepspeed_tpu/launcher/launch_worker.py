"""Per-process shim executed by the launcher.

Applies the CPU-device rehearsal override (``DS_TPU_CPU_DEVICES``) BEFORE the
user script imports anything heavy, then hands control to the user script
via ``runpy`` (the reference's ``launch.py`` execs ``python train.py``
directly).
"""

import os
import runpy
import sys


def main():
    if len(sys.argv) < 2:
        print("usage: python -m deepspeed_tpu.launcher.launch_worker "
              "<script.py> [args...]", file=sys.stderr)
        sys.exit(2)
    cpu_devices = os.environ.get("DS_TPU_CPU_DEVICES")
    if cpu_devices:
        from ..utils.jax_compat import force_cpu_devices

        force_cpu_devices(int(cpu_devices))
    script, args = sys.argv[1], sys.argv[2:]
    sys.argv = [script] + args
    runpy.run_path(script, run_name="__main__")


if __name__ == "__main__":
    main()
