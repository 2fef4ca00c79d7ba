"""What every driver and reader of the benchmark shares. Later PRs add
files beside these and change none of them."""
