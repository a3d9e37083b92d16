"""Command-line surface matching the reference exactly
(``demod_binary.c:217-445``): same flags, same long forms, same range
validation and error text, same exit codes — so BOINC ``app_info.xml``
command lines work unchanged. TPU-specific extensions use flags the
reference doesn't claim (``--batch``, ``--exact-sin``, ``--device``
repurposed for TPU ordinal).
"""

from __future__ import annotations

import sys

from . import logging as erplog
from .driver import DriverArgs, run_search
from .errors import RADPUL_EFILE, RADPUL_EMEM, RADPUL_EMISC, RADPUL_EVAL

_USAGE = """
Usage: {prog} [options], options are:

 -h, --help\t\t\tboolean\tPrint this message
 -i, --input_file\t\tstring\tThe name of the input file.
 -o, --output_file\t\tstring\tThe name of the candidate output file.
 -t, --template_bank\t\tstring\tThe name of the random template bank.
 -c, --checkpoint_file\t\tstring\tThe name of the checkpoint file.
 -l, --zaplist_file\t\tstring\tThe name of the zaplist file.
 -f, --f0\t\t\tfloat\tThe maximum signal frequency (in Hz)
 -A, --false_alarm\t\tfloat\tFalse alarm probability.
 -P, --padding\t\t\tfloat\tThe frequency over-resolution factor.
 -W, --whitening\t\tboolean\tSwitch for power spectrum whitening and line zapping.
 -B, --box\t\t\tint\tWindow width for the running median in frequeny bins.
 -D, --device\t\tinteger\tThe TPU device ID to be used.
 -z, --debug\t\t\tboolean\tRun program in debug mode.
 --batch\t\t\tint\tTemplates per device batch (TPU extension; default: auto from measured sweep / HBM model).
 --no-rescore\t\tboolean\tSkip host-oracle rescoring of emitted candidates (TPU extension).
 --mesh\t\t\tint\tShard the template bank over an N-device mesh (TPU extension; default: all visible devices).
 --profile-dir\t\tstring\tCapture a jax.profiler trace into this directory.
 --metrics-file\t\tstring\tAppend a structured metrics JSONL stream (+ run report) to this file.
 --exact-sin\t\tboolean\tUse exact sine instead of the reference LUT (TPU extension).
 --status-file\t\tstring\tProgress sink when run under the native wrapper.
 --control-file\t\tstring\tQuit/abort source when run under the native wrapper.
 --shmem\t\t\tstring\tScreensaver shared-memory segment path.
 --supervised\t\tint\tRe-exec the worker on watchdog temporary exit (rc 99), resuming from the checkpoint, up to N restarts (TPU extension).
"""


def parse_args(argv: list[str]) -> DriverArgs | int:
    """Returns DriverArgs, or an int exit code on error/help."""
    kw: dict = {}
    i = 0
    prog = "eah_brp_tpu"

    def need_value(flag: str) -> str | None:
        nonlocal i
        if i + 1 >= len(argv):
            erplog.error("Missing value for option \"%s\".\n", flag)
            return None
        value = argv[i + 1]
        i += 2
        return value

    def parse_number(flag: str, raw: str, conv):
        """None on parse failure (reported), mirroring the reference's
        validated-error path instead of a traceback."""
        try:
            return conv(raw)
        except ValueError:
            erplog.error('Couldn\'t parse value "%s" for option "%s".\n', raw, flag)
            return None

    while i < len(argv):
        a = argv[i]
        if a in ("-W", "--whitening"):
            kw["white"] = True
            i += 1
        elif a in ("-z", "--debug"):
            kw["debug"] = True
            erplog.debug("Running program in debugging mode.\n")
            i += 1
        elif a in ("-P", "--padding"):
            v = need_value(a)
            if v is None:
                return RADPUL_EVAL
            value = parse_number(a, v, float)
            if value is None:
                return RADPUL_EVAL
            if value < 1.0:
                erplog.error("Nonsense value: padding factor %g < 1.0.\n", value)
                return RADPUL_EVAL
            if value > 10.0:
                erplog.error("Nonsense value: padding factor %g > 10.0.\n", value)
                return RADPUL_EVAL
            kw["padding"] = value
        elif a in ("-B", "--box"):
            v = need_value(a)
            if v is None:
                return RADPUL_EVAL
            value = parse_number(a, v, int)
            if value is None:
                return RADPUL_EVAL
            if value < 0:
                erplog.error(
                    "Nonsense value: window size for running median %d is negative.\n",
                    value,
                )
                return RADPUL_EVAL
            if value < 2:
                # TPU-build tightening: w in {0, 1} is undefined in the
                # reference's rngmed too (rngmed.c walks a w-node list);
                # fail at the flag instead of deep inside whitening
                erplog.error(
                    "Nonsense value: window size for running median too small: %d.\n",
                    value,
                )
                return RADPUL_EVAL
            if value > 250000:
                erplog.error(
                    "Nonsense value: window size for running median too large: %d.\n",
                    value,
                )
                return RADPUL_EVAL
            kw["window"] = value
        elif a in ("-f", "--f0"):
            v = need_value(a)
            if v is None:
                return RADPUL_EVAL
            value = parse_number(a, v, float)
            if value is None:
                return RADPUL_EVAL
            if value < 0.0:
                erplog.error(
                    "Nonsense value: upper limit for search frequency %g is negative.\n",
                    value,
                )
                return RADPUL_EVAL
            if value > 16.0e3:
                erplog.error(
                    "Nonsense value: upper limit for search frequency %g > 16 kHz.\n",
                    value,
                )
                return RADPUL_EVAL
            kw["f0"] = value
        elif a in ("-A", "--false_alarm"):
            v = need_value(a)
            if v is None:
                return RADPUL_EVAL
            value = parse_number(a, v, float)
            if value is None:
                return RADPUL_EVAL
            if value < 0.0:
                erplog.error("Nonsense value: false alarm rate %g is negative.\n", value)
                return RADPUL_EVAL
            if value > 1.0:
                erplog.error("Nonsense value: false alarm rate %g > 1.0.\n", value)
                return RADPUL_EVAL
            kw["fA"] = value
        elif a in ("-i", "--input_file"):
            v = need_value(a)
            if v is None:
                return RADPUL_EFILE
            if ".binary" not in v and ".bin4" not in v:
                erplog.error(
                    "Unknown file format (extension) for input file: %s\n", v
                )
                return RADPUL_EFILE
            kw["inputfile"] = v
        elif a in ("-o", "--output_file"):
            v = need_value(a)
            if v is None:
                return RADPUL_EFILE
            kw["outputfile"] = v
        elif a in ("-c", "--checkpoint_file"):
            v = need_value(a)
            if v is None:
                return RADPUL_EFILE
            kw["checkpointfile"] = v
        elif a in ("-t", "--template_bank"):
            v = need_value(a)
            if v is None:
                return RADPUL_EFILE
            kw["templatebank"] = v
        elif a in ("-l", "--zaplist_file"):
            v = need_value(a)
            if v is None:
                return RADPUL_EFILE
            kw["zaplistfile"] = v
        elif a in ("-D", "--device"):
            v = need_value(a)
            if v is None:
                return RADPUL_EVAL
            if not v.isdigit():
                erplog.error("Invalid TPU device ID encountered: %s\n", v)
                return RADPUL_EVAL
            kw["device"] = int(v)
        elif a == "--batch":
            v = need_value(a)
            if v is None:
                return RADPUL_EVAL
            value = parse_number(a, v, int)
            if value is None or value < 1:
                erplog.error("Nonsense value: batch size must be >= 1.\n")
                return RADPUL_EVAL
            kw["batch_size"] = value
        elif a == "--mesh":
            v = need_value(a)
            if v is None:
                return RADPUL_EVAL
            value = parse_number(a, v, int)
            if value is None or value < 1:
                erplog.error("Nonsense value: mesh size must be >= 1.\n")
                return RADPUL_EVAL
            kw["mesh_devices"] = value
        elif a == "--exact-sin":
            kw["use_lut"] = False
            i += 1
        elif a == "--no-rescore":
            kw["rescore"] = False
            i += 1
        elif a == "--profile-dir":
            v = need_value(a)
            if v is None:
                return RADPUL_EFILE
            kw["profile_dir"] = v
        elif a == "--metrics-file":
            v = need_value(a)
            if v is None:
                return RADPUL_EFILE
            kw["metrics_file"] = v
        elif a in ("--status-file", "--control-file", "--shmem"):
            v = need_value(a)
            if v is None:
                return RADPUL_EFILE
            kw[a.lstrip("-").replace("-", "_")] = v
        elif a in ("-h", "--help"):
            print(_USAGE.format(prog=prog))
            return RADPUL_EMISC
        else:
            erplog.error('\nUnknown option "%s". Use \'%s --help\'.\n\n', a, prog)
            return RADPUL_EMISC

    for req in ("inputfile", "outputfile", "templatebank"):
        if req not in kw:
            erplog.error("Missing required option for %s.\n", req)
            return RADPUL_EVAL
    return DriverArgs(**kw)


def _strip_supervised(argv: list[str]) -> tuple[list[str], int | None]:
    # thin local alias: keeps the lazy-import discipline of this module
    # (nothing above arg parsing may pull jax) while the parsing logic
    # lives next to the loop it configures
    from .supervise import strip_supervised_flag

    return strip_supervised_flag(argv)


def make_adapter(args: DriverArgs):
    """BoincAdapter wired for wrapper mode when the wrapper passed status /
    control / shmem paths; plain standalone adapter otherwise."""
    from .boinc import BoincAdapter
    from .shmem import ShmemWriter

    return BoincAdapter(
        status_path=args.status_file,
        control_path=args.control_file,
        shmem=ShmemWriter(path=args.shmem) if args.shmem else None,
    )


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    # --supervised N: this process becomes the restart supervisor and the
    # actual worker runs as a child re-exec'd (minus the flag) whenever
    # the watchdog's temporary exit (rc 99) asks for another pass —
    # the native wrapper's multi-pass loop, self-hosted
    worker_argv, restart_budget = _strip_supervised(argv)
    if restart_budget is not None:
        from .supervise import run_supervised, self_cmd

        return run_supervised(
            self_cmd(worker_argv), max_restarts=max(0, restart_budget)
        )
    parsed = parse_args(argv)
    if isinstance(parsed, int):
        return parsed
    # Exit-code contract with the native wrapper (native/erp_wrapper.cpp):
    # code 1 (RADPUL_EMEM) means out-of-memory and triggers a temporary-exit
    # retry backoff — so a genuine OOM must map to it, and *no other* failure
    # may leak CPython's generic status 1 (an uncaught exception would).
    try:
        return run_search(parsed, adapter=make_adapter(parsed))
    except MemoryError as e:
        erplog.error("Out of memory: %s\n", e)
        return RADPUL_EMEM
    except Exception as e:  # deterministic failure: never report it as OOM
        if "RESOURCE_EXHAUSTED" in str(e):  # XLA's device-OOM status
            erplog.error("Device out of memory: %s\n", e)
            return RADPUL_EMEM
        import traceback

        traceback.print_exc()
        erplog.error("Unhandled error: %s\n", e)
        return RADPUL_EMISC


if __name__ == "__main__":
    sys.exit(main())
