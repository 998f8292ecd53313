"""Output tokens made per second: every token of the steps launched in
the window (one per decode row, one per final prefill chunk), over the
host seconds from the window's start, once the steps launched before it
had run, until every step launched in it had run. Clients receive the
same tokens in clumps, at the program's harvests; counting them where
they are made keeps a clump that falls either side of an edge out of
the number."""


def read(rec):
    if not rec.gen_s or not rec.launches:
        return None
    return sum(l.tokens for l in rec.launches) / rec.gen_s
