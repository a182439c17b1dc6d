"""How the readers find the program's kernels and programs in the device
trace.  Operation names are ``xplane.short_name``'s: ``<result> <opcode>
[/<custom-call target>] <shape>``.  Read off a trace of each cell on the
chip (PR 26); a refactor that renames a program shows as a metric that is
gone, not as a wrong number."""


def opcode(name: str) -> str:
    parts = name.split(" ")
    return parts[1] if len(parts) > 1 else ""


def is_hist_kernel(name: str) -> bool:
    """The Pallas kernels: in a boosting window every Mosaic custom call is
    a histogram kernel of ``ops/histogram.py`` (staged ``_hist_pallas``,
    ``fused_round``); the trace carries no kernel name of its own."""
    return opcode(name) == "custom-call/tpu_custom_call"


def is_round_program(name: str) -> bool:
    """The 25-round program ``_boost_binned`` dispatches."""
    return name.startswith("jit_k_rounds_body")


def is_all_reduce(name: str) -> bool:
    return opcode(name).startswith("all-reduce")


def is_sort(name: str) -> bool:
    return opcode(name) == "sort"
