"""Seconds covered by at least one lifecycle span that began before the
window (the first `Request.t_submit`, or in a training cell the ring's last
`train_step` / `train_scan_chunk` event): the set-up the PROGRAM accounts
for. `setup_s` of the same run less this is the harness's share, which has no
span: reaching the chip, importing, generating the schedule, a training
cell's reference forward and gradient and its comparison of step 1."""
NAME, UNIT = "setup_program_s", "s"
LAYER, MOVES, SOURCE = "model + compile", "setup_s", "program_span"


def read(ctx):
    from benchmark import setup_reduce as sr

    red = sr.for_ctx(ctx)
    return sr.union_s(red["spans"]) if red else None
