"""Share of the bytes the window's transfers put on the fabric that is
fixed recurrent state (Eq. 2'): 100 x the sum of each request's
``state_bytes`` over the sum of its ``s_eff``, over the requests of the
window's simulations that moved any bytes.  A program whose request
records carry no ``state_bytes`` reads nothing."""


def read(run):
    st = run.notes.get("state")
    records = [rs for recs in getattr(st, "records", ()) for rs in recs
               if rs.s_eff > 0.0]
    if not records or not hasattr(records[0], "state_bytes"):
        return None
    return 100.0 * sum(rs.state_bytes for rs in records) \
        / sum(rs.s_eff for rs in records)
