"""Checkpoint layer: tokens/s/chip over whole save cycles (each from one
save's return to the next save's return), stalls included — what a job that
saves every `ckpt_every` steps really gets."""


def read(run):
    return run["window"].get("goodput_tokens_per_s_per_chip")
