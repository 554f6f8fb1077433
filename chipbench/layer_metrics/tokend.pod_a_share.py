"""Pod A's share of the window as tokend charged it: A's charged_total_ms
delta over the window's length."""

LAYER = "token runtime"
UNIT = "%"
MOVES = "ttft_tail_ms"


def read(run):
    record = run["record"]
    stat_a = record["stat"][run["pod_a"]]
    return stat_a["charged_total_ms"] / (record["seconds"] * 1e3) * 100.0
