"""Share of the anti-entropy winner kernel's roofline it reaches, in %.

The least time the chip could take over the kernel's calls in the traced
window, divided by the kernel's summed device time. Per call the need is
the larger of bytes over the HBM bandwidth and operations over the peak:
the sender keys (publish time, publisher, approval count; 4 bytes each)
read once, the receiver-by-sender candidate mask read once, and the
winner index and merged counter (4 bytes each) written once per receiver
and row; one lexicographic comparison step (6 operations) per receiver,
sender and row. A call in the event engine's ``advance`` reduces all
``num_nodes`` receivers; a call in the union fold ``merge_all`` reduces
one. The kernel is found by its name in the device ops: the custom call
``gossip_winner_pallas.<n>`` (``kernels/gossip_merge.py``).
"""

KERNEL = "gossip_winner_pallas"
RECEIVERS = {"advance": None, "merge_all": 1}    # None: every node receives


def need_s(receivers: int, senders: int, rows: int, peaks: dict) -> float:
    nbytes = 4 * (3 * senders * rows + receivers * senders + 2 * receivers * rows)
    ops = 6 * receivers * senders * rows
    return max(nbytes / peaks["hbm_bytes_per_s"], ops / peaks["flops_per_s"])


def read(ctx):
    dg = ctx["cell"].config["dagfl"]
    nodes, rows = dg["num_nodes"], dg["capacity"]
    spent, need = 0.0, 0.0
    for (program, op), (secs, calls) in ctx["trace"]["ops_by_program"].items():
        if KERNEL not in op:
            continue
        spent += secs
        if program in RECEIVERS:
            rr = RECEIVERS[program] or nodes
            need += calls * need_s(rr, nodes, rows, ctx["peaks"])
        else:
            return None          # a call whose shapes this reader does not know
    if spent <= 0:
        return None
    return 100.0 * need / spent
