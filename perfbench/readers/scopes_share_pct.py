"""``scope_share_pct`` over several blocks at once: the share of the
device's busy time in the traced window under ANY of ``scopes``, each
matched as ``scope_share_pct`` matches its one (whole components of the
operation's ``jax.named_scope`` path).  For a block part of whose work
the compiler lowers to a custom call that carries no path but its own
name: the chip's grouped matmul shows as ``ragged-dot-none:``, whatever
block asked for it, so the experts' share is the ``moe`` block's plus that
name's."""


def inside(trace, scopes) -> float:
    """Seconds of self time under any of ``scopes``; each operation counts
    once."""
    return sum(seconds for path, seconds in trace["by_scope"].items()
               if any(f"/{scope}/" in f"/{path}/" for scope in scopes))


def read(observed, scopes):
    trace = observed.get("trace")
    if not trace or not trace.get("by_scope"):
        return None
    return 100.0 * inside(trace, scopes) / trace["busy_s"]
