"""How late the load generator sent: actual send time - due time, 95th
percentile over the window's requests.  A starved generator must not read
as a fast server."""
from benchmark import harness as H


def read(ctx):
    late = [r["send_t"] - r["due_t"] for r in ctx.get("sample", [])]
    return 1e3 * H.percentile(late, 95) if late else None
