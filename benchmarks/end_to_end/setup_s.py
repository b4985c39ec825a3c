"""Process start (the first statement of run.py) to the end of warm-up."""



def read(run):
    return run["setup_s"]
