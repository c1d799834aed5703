"""Mean device time of one user-lane program in the traced slice:
seconds over count of the modules named for that lane
(``jit_users_topk_*``, ``jit_two_topk``). Against ``dispatch_p50_us``
(host clock, call -> ``block_until_ready``) it gives the launch and
sync overhead of a dispatch. Nothing where no trace was taken, and on
a program whose serving modules are all called ``jit_prog``."""

PREFIXES = ("jit_users_topk", "jit_two_topk")


def read(r):
    t = r.get("trace")
    if not t:
        return None
    lanes = [m for name, m in t["modules"].items()
             if name.startswith(PREFIXES)]
    count = sum(m["count"] for m in lanes)
    if count <= 0:
        return None
    return 1e6 * sum(m["seconds"] for m in lanes) / count
