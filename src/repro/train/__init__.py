from repro.train import checkpoint, loop, optim
