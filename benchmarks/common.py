import os
import subprocess
import sys
import textwrap
import time


def time_call(fn, warmup=1, iters=5):
    for _ in range(warmup):
        fn()
    t0 = time.monotonic()
    for _ in range(iters):
        fn()
    return (time.monotonic() - t0) / iters * 1e6      # us/call


def peak_rss_of(snippet: str) -> float:
    """Run a python snippet in a subprocess, return peak RSS in MB.

    Reads VmHWM from /proc/self/status: unlike ru_maxrss (which Linux
    carries across exec, so children inherit the parent's peak), VmHWM
    tracks the post-exec address space only."""
    prog = textwrap.dedent(snippet) + textwrap.dedent("""
        peak = 0
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM"):
                    peak = int(line.split()[1])
        print("PEAK_RSS_KB", peak)
    """)
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    # the child measures host memory only; on the CPU it never competes
    # with this process for the accelerator
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, check=True)
    for line in out.stdout.splitlines():
        if line.startswith("PEAK_RSS_KB"):
            return float(line.split()[1]) / 1024.0
    raise RuntimeError(out.stdout + out.stderr)


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}")
