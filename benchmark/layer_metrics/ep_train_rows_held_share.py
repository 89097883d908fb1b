"""Share of the (token, expert) assignments that landed on the experts held
here: the train step's own count (`moe_assignments_total` of
`FFModel.last_step_breakdown`: counted inside the jitted step, summed over the
expert layers and over the last round's steps) over tokens x
`num_experts_per_tok` x expert layers x those steps. 100 x held /
`router_experts` = 12.5 where routing is even; it says whether the router
drifted toward or away from the held experts over the window's Adam steps on
random tokens, which moves the step's time (the held rows are what the expert
matmuls multiply). Lower as `ep_experts_hit_share` is: fewer rows here is
less work here."""
NAME, UNIT = "ep_train_rows_held_share", "%"
LAYER, MOVES, SOURCE = "moe op", "train_tokens_per_s", "program_counter"


def read(ctx):
    bd = ctx.get("last_step_breakdown") or {}
    z = ctx.get("sizes") or {}
    if not bd.get("moe_steps") or "moe_assignments_total" not in bd \
            or "num_experts_per_tok" not in z:
        return None
    layers = z["num_hidden_layers"] - z["first_k_dense_replace"]
    picks = (ctx["tokens_per_step"] * z["num_experts_per_tok"] * layers
             * bd["moe_steps"])
    return 100.0 * bd["moe_assignments_total"] / picks
