"""Mean seconds from submission to lane admission (``Ticket.queue_wait``)
of the queries sent in the window."""


def read(rec):
    waits = [q["queue_wait_s"] for q in rec["queries"]
             if q["queue_wait_s"] is not None]
    return sum(waits) / len(waits) if waits else None
