"""The top node price of the fused node match after a round, in units of
its last phase's ``eps``, averaged over the window rounds that record it
(the program's ``fused_node_price_top``).  At ``2**24`` and above f32 can
no longer raise a price by ``eps``: a hard term has leaked into the
prices."""


def read(record):
    tops = [r["stats"]["fused_node_price_top"] for r in record["rounds"]
            if "fused_node_price_top" in r["stats"]]
    return sum(tops) / len(tops) if tops else None
