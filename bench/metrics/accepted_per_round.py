"""Output tokens accepted per verify round a request took part in, over the
device loops of the window: tokens generated over active row-rounds."""


def read(run):
    tok = rounds = 0
    for s in run.steps_in_window():
        for _, active, n in s.rows:
            tok += n
            rounds += active
    return tok / rounds if rounds else None
