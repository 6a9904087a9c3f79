"""The yardstick: everything the benchmark needs that is not the system
under test. Later PRs add files beside these and never edit them."""
