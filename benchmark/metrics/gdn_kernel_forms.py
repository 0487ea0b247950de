"""How many of the delta rule's two forms ran on a Pallas kernel, 0-2: the
training sweep (the train step's stat `linear_attn/rule_on_kernel`) and the
decode step (the generator's counter `gdn_step_on_kernel`), both set at
trace time from what the program picked (`linear_attention.
chunk_kernel_form` / `step_kernel_form`).  None where the program keeps
neither."""


def read(run):
    step = run.steps[-1]
    forms = (step["stats"].get("actor_train/linear_attn/rule_on_kernel"),
             step["pool"].get("gdn_step_on_kernel"))
    if all(f is None for f in forms):
        return None
    return sum(float(f) for f in forms if f is not None)
