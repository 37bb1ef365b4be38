#!/usr/bin/env python3
"""Native-build gate: rebuild ``libdqcsv.so`` from source, smoke it, and
verify the runtime SIMD dispatch degrades cleanly.

CI/tooling guard for the ingest tentpole (ISSUE 7): the loader builds
``native/libdqcsv.so`` on first use, so a source change that no longer
compiles fails loudly there — but one that compiles and mis-parses would
ride along silently. This script:

1. rebuilds the shared library from ``native/csvparse.cpp`` into a temp
   directory (``native/libdqcsv.so`` itself is never touched),
2. builds and runs ``native/smoke_test.cpp`` against it, which
   cross-checks v1 / v2-scalar / best-SIMD-tier / chunk-parallel /
   streaming output bit-wise,
3. loads the fresh library via ctypes and verifies runtime dispatch:
   ``dq_effective_simd`` clamps every explicit tier request (0/1/2) to
   what the CPU supports, ``DQCSV_SIMD=off`` forces the scalar tier, and
   a parse under each requested tier returns identical bytes — i.e. on a
   CPU without AVX-512 the avx512 request falls back cleanly instead of
   SIGILLing.

4. (ISSUE 8) builds and runs **sanitizer arms** over the same sources:
   an ASan+UBSan binary (smoke_test.cpp + csvparse.cpp compiled
   together, ``-fno-sanitize-recover=all`` so any finding is fatal)
   running the full smoke cross-check on a generated multi-thousand-row
   CSV, and a TSan binary running the smoke's *threaded stream parity
   grid* (``smoke <file> grid``: {chunk size} x {1,2,4 threads} over
   the chunk-parallel ``dq_stream`` path) on a multi-MB file so the
   parse threads, chunk cutting, and cross-chunk integral backfill see
   a real thread schedule under the race detector. Each arm SKIPs
   cleanly when the toolchain cannot link that sanitizer.

Exit codes: 0 = pass (or clean SKIP when no C++ toolchain is present —
the pure-Python engine is a supported configuration), 1 = failure.
Wired as a tier-1 test in tests/test_ingest.py.

Usage::

    python scripts/check_native_build.py [--keep] [--no-sanitize]
"""

from __future__ import annotations

import argparse
import ctypes
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")


def find_cxx():
    """First usable C++ compiler, honoring $CXX like the Makefile."""
    for cxx in (os.environ.get("CXX"), "g++", "c++", "clang++"):
        if cxx and shutil.which(cxx):
            return cxx
    return None


def run(cmd, **kw):
    return subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                          **kw)


def build(cxx: str, tmp: str) -> str | None:
    """Compile csvparse.cpp -> tmp/libdqcsv.so; None on failure."""
    so = os.path.join(tmp, "libdqcsv.so")
    # baseline x86-64 like the Makefile's library target: the build still
    # carries every SIMD tier via per-function targets
    flags = ["-O2", "-Wall", "-fPIC", "-std=c++17", "-pthread"]
    p = run([cxx, *flags, "-shared", "-o", so,
             os.path.join(NATIVE, "csvparse.cpp")])
    if p.returncode != 0:
        print(f"FAIL: csvparse.cpp does not compile:\n{p.stderr[-4000:]}")
        return None
    return so


def build_and_run_smoke(cxx: str, tmp: str, so: str) -> bool:
    smoke = os.path.join(tmp, "smoke")
    p = run([cxx, "-O2", "-std=c++17", "-pthread", "-o", smoke,
             os.path.join(NATIVE, "smoke_test.cpp"),
             f"-L{tmp}", "-ldqcsv", f"-Wl,-rpath,{tmp}"])
    if p.returncode != 0:
        print(f"FAIL: smoke_test.cpp does not compile:\n{p.stderr[-4000:]}")
        return False
    data = os.path.join(REPO, "data", "dataset-abstract.csv")
    if not os.path.exists(data):
        print(f"WARN: {data} missing; skipping smoke run")
        return True
    for env_simd in (None, "off"):
        env = dict(os.environ)
        env.pop("DQCSV_SIMD", None)
        if env_simd is not None:
            env["DQCSV_SIMD"] = env_simd
        p = run([smoke, data], env=env)
        tag = f"DQCSV_SIMD={env_simd or '<unset>'}"
        if p.returncode != 0:
            print(f"FAIL: smoke run ({tag}):\n{p.stdout}{p.stderr}")
            return False
        print(f"smoke OK ({tag}): {p.stdout.splitlines()[0]}")
    return True


def check_dispatch(so: str, tmp: str) -> bool:
    """Runtime-dispatch invariants on the freshly built library."""
    lib = ctypes.CDLL(so)
    lib.dq_effective_simd.restype = ctypes.c_int
    lib.dq_effective_simd.argtypes = [ctypes.c_int]
    pd = ctypes.POINTER(ctypes.c_double)
    lib.dq_parse_numeric_csv_v2.restype = ctypes.c_longlong
    lib.dq_parse_numeric_csv_v2.argtypes = [
        ctypes.c_char_p, ctypes.c_char, ctypes.c_char, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(pd),
        ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_char))]
    lib.dq_free.restype = None
    lib.dq_free.argtypes = [ctypes.c_void_p]

    cpu = lib.dq_effective_simd(2)  # ceiling: explicit avx512 clamps here
    ok = True
    for req in (0, 1, 2):
        eff = lib.dq_effective_simd(req)
        if eff > min(req, cpu):
            print(f"FAIL: dispatch: request {req} -> tier {eff} "
                  f"(cpu ceiling {cpu})")
            ok = False
    if lib.dq_effective_simd(0) != 0:
        print("FAIL: dispatch: scalar request did not pin tier 0")
        ok = False
    if not ok:
        return False
    print(f"dispatch OK: cpu ceiling tier={cpu}, "
          f"requests 0/1/2 -> {[lib.dq_effective_simd(r) for r in (0, 1, 2)]}")

    # Every requested tier — including ones past the CPU ceiling, which
    # MUST fall back rather than SIGILL — parses to identical bytes.
    csv = os.path.join(tmp, "dispatch.csv")
    with open(csv, "w") as f:
        for i in range(4097):  # > one 4 KiB word block, mixed shapes
            f.write(f"{i},{i}.{i % 100:02d},-{i}e-2,,{i * 7 % 997}\n")
    outs = []
    for req in (0, 1, 2):
        data_p = pd()
        ncols = ctypes.c_longlong(0)
        flags_p = ctypes.POINTER(ctypes.c_char)()
        rows = lib.dq_parse_numeric_csv_v2(
            csv.encode(), b",", b'"', 0, req, 2, ctypes.byref(data_p),
            ctypes.byref(ncols), ctypes.byref(flags_p))
        if rows <= 0:
            print(f"FAIL: parse under simd request {req}: rows={rows}")
            return False
        nvals = int(ncols.value) * int(rows)
        outs.append((rows, ncols.value,
                     ctypes.string_at(data_p, nvals * 8),
                     ctypes.string_at(flags_p, int(ncols.value))))
        lib.dq_free(data_p)
        lib.dq_free(flags_p)
    if not all(o == outs[0] for o in outs[1:]):
        print("FAIL: simd tiers disagree bit-wise on the dispatch probe")
        return False
    print(f"tier parity OK: rows={outs[0][0]} cols={outs[0][1]} "
          "(scalar == avx2-request == avx512-request)")
    return True


def _sanitizer_csv(tmp: str, rows: int) -> str:
    """Mixed-shape numeric CSV big enough to engage the chunk-parallel
    threads (the native layer budgets ~1 thread per MB)."""
    path = os.path.join(tmp, f"san_{rows}.csv")
    if not os.path.exists(path):
        with open(path, "w") as f:
            for i in range(rows):
                f.write(f"{i},{i}.{i % 100:02d},-{i}e-2,,{i * 7 % 997}\n")
    return path


def _sanitizer_supported(cxx: str, tmp: str, flag: str) -> bool:
    """Can this toolchain compile AND link `flag`? (gcc happily accepts
    -fsanitize=thread at compile time on hosts with no libtsan)."""
    probe_src = os.path.join(tmp, "san_probe.cpp")
    if not os.path.exists(probe_src):
        with open(probe_src, "w") as f:
            f.write("int main() { return 0; }\n")
    p = run([cxx, flag, "-o", os.path.join(tmp, "san_probe"), probe_src])
    return p.returncode == 0


def sanitizer_arm(cxx: str, tmp: str, kind: str) -> bool:
    """Build smoke+parser under a sanitizer and run it; True = pass/SKIP.

    kind 'asan': address+undefined, full smoke cross-check, SIMD tiers on
    (``-march=native`` when available) so the AVX kernels' loads/stores
    get bounds-checked too. kind 'tsan': thread sanitizer over the
    threaded stream parity grid on a multi-MB file (baseline arch — the
    racing surface is the thread protocol, not the SIMD kernels).
    """
    flag = {"asan": "-fsanitize=address,undefined",
            "tsan": "-fsanitize=thread"}[kind]
    if not _sanitizer_supported(cxx, tmp, flag):
        print(f"SKIP: {kind}: toolchain cannot link {flag}")
        return True
    exe = os.path.join(tmp, f"smoke_{kind}")
    flags = ["-O1", "-g", flag, "-fno-sanitize-recover=all",
             "-std=c++17", "-pthread"]
    if kind == "asan":
        probe = run([cxx, "-march=native", "-E", "-x", "c", "/dev/null"])
        if probe.returncode == 0:
            flags.append("-march=native")
    p = run([cxx, *flags, "-o", exe,
             os.path.join(NATIVE, "csvparse.cpp"),
             os.path.join(NATIVE, "smoke_test.cpp")])
    if p.returncode != 0:
        print(f"FAIL: {kind} build:\n{p.stderr[-4000:]}")
        return False
    csv = _sanitizer_csv(tmp, 60_000 if kind == "asan" else 120_000)
    argv = [exe, csv] + (["grid"] if kind == "tsan" else [])
    p = run(argv)
    if p.returncode != 0:
        print(f"FAIL: {kind} run:\n{p.stdout[-2000:]}{p.stderr[-4000:]}")
        return False
    print(f"{kind} OK: {p.stdout.splitlines()[-1]}")
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--keep", action="store_true",
                    help="keep the temp build directory")
    ap.add_argument("--no-sanitize", action="store_true",
                    help="skip the ASan/UBSan and TSan arms")
    args = ap.parse_args(argv)

    cxx = find_cxx()
    if cxx is None:
        print("SKIP: no C++ toolchain (CXX/g++/c++/clang++) on PATH")
        return 0

    tmp = tempfile.mkdtemp(prefix="dqcsv_build_")
    try:
        so = build(cxx, tmp)
        if so is None:
            return 1
        if not build_and_run_smoke(cxx, tmp, so):
            return 1
        if not check_dispatch(so, tmp):
            return 1
        if not args.no_sanitize:
            if not sanitizer_arm(cxx, tmp, "asan"):
                return 1
            if not sanitizer_arm(cxx, tmp, "tsan"):
                return 1
        print("PASS: native rebuild + smoke + runtime dispatch"
              + ("" if args.no_sanitize else " + sanitizer arms"))
        return 0
    finally:
        if args.keep:
            print(f"build kept at {tmp}")
        else:
            shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
