"""The route stage's share of its roofline: the least time the chip could
take (the bytes the route must move over the HBM peak; the route is bound by
memory) over the measured device time per window."""
import peaks


def read(ctx):
    total, calls = ctx["trace"]["modules"].get("bench_route", (0.0, 0))
    if not calls:
        return None
    least = peaks.route_bytes(ctx["traffic"]["window_packets"]) / peaks.peak(
        ctx["kind"], "hbm_bytes_s")
    return 100.0 * least / (total / calls)
