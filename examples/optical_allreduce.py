"""Execute Vermilion's schedule JAX-natively: the optical circuits of one
period become lax.ppermute steps over a 'pod' mesh axis spanning every
device JAX sees (the chips of a TPU host; at least two are needed).

    PYTHONPATH=src python examples/optical_allreduce.py

On a CPU-only machine, give the CPU backend fake devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/optical_allreduce.py
"""
import jax

from repro.core.optical import run_schedule_demo


def main():
    res = run_schedule_demo()
    print("Vermilion schedule executed via lax.ppermute on "
          f"{len(jax.devices())} devices:")
    for kk, vv in res.items():
        print(f"  {kk}: {'PASS' if vv else 'FAIL'}")
    assert all(res.values())


if __name__ == "__main__":
    main()
