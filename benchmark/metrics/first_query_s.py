"""Wall of the first pass over the cell's questions in the new process, each
to a materialised device result, with the compile cache as the run finds it."""


def read(obs):
    return obs["first_pass"]["wall_s"]
